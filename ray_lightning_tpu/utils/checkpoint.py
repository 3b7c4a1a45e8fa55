"""Sharded (orbax-backed) checkpointing — no host gather.

The parity checkpoint path (core/trainer.py save_checkpoint) mirrors the
reference: gather the full state to the host, serialize one blob
(reference analog: to_state_stream/torch.save, util.py:71-90).  That is
fine at BoringModel scale and wrong at pod scale — gathering a sharded
1.3B+ train state funnels every shard through one host's memory and one
file.

:class:`ShardedCheckpointer` is the TPU-native alternative (SURVEY.md §5
flags exactly this: "state streams must gather sharded (ZeRO) optimizer
state or write per-host shards"): each process writes only the array
shards it owns (orbax OCDBT format), saves run asynchronously behind the
training step, and restore re-shards directly into the CURRENT mesh —
resuming on a different world size or strategy never materializes the
full state on any single host (the reference's resume-with-fewer-workers
case, test_ddp_sharded.py:119-138, at scales where the gather path
cannot).

Paths may be local or fsspec-style remote (gs://...) — orbax talks to
GCS natively, matching the "pods have no shared local FS" default.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax

from ray_lightning_tpu.telemetry import span


def _manager(directory: str, async_save: bool, max_to_keep: Optional[int]):
    import orbax.checkpoint as ocp
    if "://" not in directory:
        directory = os.path.abspath(directory)
    options = ocp.CheckpointManagerOptions(
        max_to_keep=max_to_keep,
        enable_async_checkpointing=async_save,
    )
    # item names/handlers declared up front: a FRESH manager over an
    # existing directory can then answer item_metadata() (the elastic
    # reshard path reads saved shapes before restoring) and restore a
    # subset of items, instead of failing handler inference
    return ocp.CheckpointManager(
        directory, options=options,
        item_names=("state", "meta"),
        item_handlers={"state": ocp.StandardCheckpointHandler(),
                       "meta": ocp.JsonCheckpointHandler()})


def abstract_like(state: Any, shardings: Any) -> Any:
    """ShapeDtypeStruct pytree carrying the target shardings — the
    restore target that tells orbax where every shard should land."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, shardings)


class ShardedCheckpointer:
    """Per-shard async checkpoint manager over a step-numbered directory.

    Layout: ``<directory>/<step>/{state,meta}`` (orbax OCDBT).  ``state``
    is the TrainState pytree written shard-by-shard; ``meta`` is a small
    JSON dict (epoch, global_step, strategy, ...).
    """

    def __init__(self, directory: str, async_save: bool = True,
                 max_to_keep: Optional[int] = None):
        self.directory = directory
        self.max_to_keep = max_to_keep
        self._mgr = _manager(directory, async_save, max_to_keep)

    # -- save ------------------------------------------------------------

    def save(self, step: int, state: Any, meta: Optional[dict] = None):
        """Write ``state`` under ``step``.  Returns immediately when
        async (the copy out of device memory happens first; the disk
        write proceeds behind the training loop).  Saving a step that
        already exists is a no-op (two cadences — e.g. every-N-steps and
        every-epoch — can land on the same global step)."""
        import orbax.checkpoint as ocp
        if int(step) in self._mgr.all_steps():
            return
        # the span covers only the blocking part of an async save (the
        # device→host copy); the disk write proceeds behind training
        with span("checkpoint", step=int(step), sharded=True):
            self._mgr.save(int(step), args=ocp.args.Composite(
                state=ocp.args.StandardSave(state),
                meta=ocp.args.JsonSave(dict(meta or {}))))

    def wait(self) -> None:
        """Block until in-flight async saves hit disk."""
        with span("checkpoint_wait"):
            self._mgr.wait_until_finished()

    def saving_in_progress(self) -> bool:
        """True while a previous async save is still writing — the
        elastic snapshotter's backpressure probe (elastic/snapshot.py).
        Conservatively False on orbax builds without the query (a save
        then simply blocks inside orbax instead of being skipped)."""
        probe = getattr(self._mgr, "is_saving_in_progress", None)
        if probe is None:
            return False
        try:
            return bool(probe())
        except Exception:
            return False

    # -- restore ---------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        return sorted(self._mgr.all_steps())

    def saved_state_metadata(self, step: Optional[int] = None):
        """Shapes/dtypes of the SAVED ``state`` tree (a nested dict of
        array metadata, no array data read) — what the elastic reshard
        path compares the restore target against so a topology change
        never restores silently wrong (elastic/reshard.py).  ``None``
        when the manager cannot answer (old orbax, remote quirk)."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        try:
            md = self._mgr.item_metadata(int(step))
            # orbax 0.11.32: the item's metadata is a TreeMetadata
            # whose ``tree`` is the nested dict
            return md.state.tree
        except Exception:
            return None

    def restore(self, abstract_state: Any,
                step: Optional[int] = None) -> tuple[Any, dict]:
        """Load ``(state, meta)`` at ``step`` (default: latest), sharded
        per ``abstract_state``'s shardings — which may describe a
        different mesh than the one that saved."""
        import orbax.checkpoint as ocp
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"No checkpoint steps under {self.directory}")
        out = self._mgr.restore(int(step), args=ocp.args.Composite(
            state=ocp.args.StandardRestore(abstract_state),
            meta=ocp.args.JsonRestore()))
        return out.state, dict(out.meta or {})

    def close(self) -> None:
        self._mgr.close()

    # -- detection -------------------------------------------------------

    @staticmethod
    def _dir_entries(path: str) -> "Optional[list[str]]":
        # Detection must degrade to "not a sharded checkpoint" on ANY
        # listing failure: remote fsspec backends (gcsfs etc.) raise
        # non-OSError exceptions, and this runs on every restore.
        try:
            if "://" in path:
                import fsspec
                fs, p = fsspec.core.url_to_fs(path)
                if not fs.isdir(p):
                    return None
                return [os.path.basename(e.rstrip("/")) for e in fs.ls(p)]
            if os.path.isdir(path):
                return os.listdir(path)
        except Exception:
            pass
        return None

    @staticmethod
    def split_step_dir(path: str) -> "tuple[str, Optional[int]]":
        """``.../cks/42`` → ``(.../cks, 42)``; a root dir → ``(path,
        None)``.  Users naturally pass either the manager root or one
        specific step directory."""
        base = os.path.basename(path.rstrip("/"))
        if base.isdigit():
            return path.rstrip("/")[: -len(base)].rstrip("/"), int(base)
        return path, None

    @classmethod
    def is_sharded_checkpoint(cls, path: str) -> bool:
        """True when ``path`` is an orbax checkpoint directory — either
        the step-numbered root or one step inside it (vs the single-file
        msgpack format of Trainer.save_checkpoint)."""
        names = cls._dir_entries(path)
        if names is None:
            return False
        root, step = cls.split_step_dir(path)
        if step is not None:
            # a specific step dir: saved items live directly inside
            return any(n in ("state", "meta", "_CHECKPOINT_METADATA")
                       for n in names)
        return any(n.isdigit() for n in names)
