"""GPT-2's operation counts under their old address, for the program's
tier-1 test that reads them here (``tests/test_goodput.py``, which a
``benchmark`` PR may not edit).  The functions live in the family's
adapter; nothing of the harness imports this file."""

from chipbench.adapters.gpt2 import (matmul_params,  # noqa: F401
                                     train_flops_per_token)
