"""CSV metrics logging (PL CSVLogger analog): metrics.csv written under
the trainer root, rank-zero-gated in distributed fits, disabled with
logger=False, custom loggers pluggable."""

import csv
import os
import pickle

from ray_lightning_tpu import Trainer
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.utils.logger import CSVLogger

from tests.utils import cpu_plugin


def _read(path):
    with open(path) as f:
        return list(csv.DictReader(f))


def test_csv_logger_unions_columns(tmp_path):
    lg = CSVLogger(str(tmp_path))
    lg.log_metrics({"loss": 1.0}, step=1)
    lg.log_metrics({"loss": 0.5, "val_loss": 0.7}, step=2)
    rows = _read(lg.path)
    assert rows[0]["loss"] == "1.0" and rows[0]["val_loss"] == ""
    assert rows[1]["val_loss"] == "0.7"


def test_fit_writes_metrics_csv(tmp_path, seed):
    trainer = Trainer(max_epochs=2, limit_train_batches=4,
                      limit_val_batches=2, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      log_every_n_steps=2,
                      default_root_dir=str(tmp_path))
    trainer.fit(BoringModel())
    path = os.path.join(str(tmp_path), "logs", "metrics.csv")
    assert os.path.exists(path)
    rows = _read(path)
    assert any(r.get("loss") for r in rows)
    assert any(r.get("val_loss") for r in rows)  # eval metrics logged too


def test_logger_false_writes_nothing(tmp_path, seed):
    trainer = Trainer(max_epochs=1, limit_train_batches=2,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0, logger=False,
                      default_root_dir=str(tmp_path))
    trainer.fit(BoringModel())
    assert not os.path.exists(os.path.join(str(tmp_path), "logs"))


def test_custom_logger_object(tmp_path, seed):
    class Capture:
        def __init__(self):
            self.events = []

        def log_metrics(self, metrics, step):
            self.events.append((step, dict(metrics)))

    cap = Capture()
    trainer = Trainer(max_epochs=1, limit_train_batches=4,
                      limit_val_batches=0, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0, logger=cap,
                      log_every_n_steps=1,
                      default_root_dir=str(tmp_path))
    trainer.fit(BoringModel())
    assert len(cap.events) >= 4
    assert all("loss" in m for _s, m in cap.events[:4])


def test_distributed_fit_rank_zero_writes(tmp_path, seed):
    """With actors, rank 0's worker writes the CSV (shared FS here);
    the file exists and has training rows."""
    trainer = Trainer(max_epochs=1, limit_train_batches=4,
                      limit_val_batches=1, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      log_every_n_steps=2,
                      plugins=[cpu_plugin(2)],
                      default_root_dir=str(tmp_path))
    trainer.fit(BoringModel())
    path = os.path.join(str(tmp_path), "logs", "metrics.csv")
    assert os.path.exists(path)
    assert any(r.get("loss") for r in _read(path))


def test_fit_then_validate_preserves_file(tmp_path, seed):
    """A second dispatch (fresh pickled logger state) must append to the
    run's metrics.csv, not truncate it."""
    model = BoringModel()
    trainer = Trainer(max_epochs=1, limit_train_batches=4,
                      limit_val_batches=1, num_sanity_val_steps=0,
                      enable_checkpointing=False, seed=0,
                      log_every_n_steps=2,
                      default_root_dir=str(tmp_path))
    trainer.fit(model)
    path = os.path.join(str(tmp_path), "logs", "metrics.csv")
    rows_after_fit = len(_read(path))
    assert rows_after_fit > 0
    # a pickled copy of the run's logger (what a second dispatch actually
    # ships, plugins/xla.py) continues the same file: fresh _started
    # state, same _run_id
    fresh = pickle.loads(pickle.dumps(trainer.logger))
    fresh._started = False
    fresh._fields = ["step"]
    fresh.log_metrics({"extra_metric": 1.0}, step=99)
    rows = _read(path)
    assert len(rows) == rows_after_fit + 1      # appended, not truncated
    assert rows[-1]["extra_metric"] == "1.0"
    assert any(r.get("loss") for r in rows)     # old rows intact


def test_new_run_truncates_stale_file(tmp_path):
    """A brand-new logger pointed at a dir holding another run's
    metrics.csv starts fresh instead of appending to the stale file."""
    old = CSVLogger(str(tmp_path))
    old.log_metrics({"loss": 1.0}, step=0)
    old.log_metrics({"loss": 0.5}, step=1)
    path = os.path.join(str(tmp_path), "logs", "metrics.csv")
    assert len(_read(path)) == 2

    new = CSVLogger(str(tmp_path))            # different run id
    new.log_metrics({"acc": 0.9}, step=0)
    rows = _read(path)
    assert len(rows) == 1                     # truncated, not appended
    assert rows[0]["acc"] == "0.9"
    assert "loss" not in rows[0]


def test_a_grown_header_keeps_columns_another_writer_put_there(tmp_path):
    """Two runs that share a root dir (tests under the default
    ``rlt_logs``, side by side): the file one writer re-headers may hold
    columns the other wrote since.  They are kept, not a ValueError."""
    mine = CSVLogger(str(tmp_path))
    mine.log_metrics({"loss": 1.0}, step=0)
    other = CSVLogger(str(tmp_path))          # starts the file afresh
    other.log_metrics({"val_acc": 0.9}, step=0)
    mine.log_metrics({"loss": 0.5, "val_loss": 0.7}, step=1)
    rows = _read(mine.path)
    assert [r["val_acc"] for r in rows] == ["0.9", ""]
    assert rows[1]["loss"] == "0.5" and rows[1]["val_loss"] == "0.7"
