"""Metrics loggers.

The reference inherits PL's logger stack (metrics files under the
trainer's root dir; rank-zero gating via ``rank_zero_only.rank``,
ray_ddp.py:405).  Here :class:`CSVLogger` is the built-in equivalent of
PL's CSVLogger: one ``metrics.csv`` under ``<root>/logs/``, a row per
logging event, columns unioned across events.  ``Trainer(logger=True)``
(the default) installs it; ``logger=False`` disables; any object with a
``log_metrics(dict, step)`` method slots in as a custom logger.

Rank-zero gating happens in the trainer (only rank 0's logger writes),
so files on a shared FS are written once per run, like the reference's
rank-zero-gated PL loggers.

Distributed caveat for CUSTOM loggers: with actor plugins the trainer is
pickled into the workers, so ``log_metrics`` fires on rank-0's *copy* —
a logger must persist externally (file/DB/service, as CSVLogger does);
in-memory state never returns to the driver (only ``callback_metrics``
does, via the result relay).
"""

from __future__ import annotations

import csv
import os
import tempfile
import uuid


class CSVLogger:
    """Append-only CSV metrics log (PL CSVLogger analog).

    O(1) memory: rows append straight to disk; when the column set grows
    (e.g. the first val_* metrics after an epoch) the existing file is
    read back once and rewritten under the new header, so late-appearing
    metrics still land in one coherent table.
    """

    def __init__(self, save_dir: str, name: str = "logs"):
        self.save_dir = save_dir
        self.name = name
        self._fields: list[str] = ["step"]
        self._started = False
        # Identifies THIS logical run across pickled copies (the trainer
        # is re-pickled into workers per dispatch, so fit→validate uses
        # two copies of this object that must share one file) while
        # distinguishing a genuinely new run pointed at the same root
        # dir, which must truncate rather than append to the stale file.
        self._run_id = uuid.uuid4().hex

    @property
    def log_dir(self) -> str:
        return os.path.join(self.save_dir, self.name)

    @property
    def path(self) -> str:
        return os.path.join(self.log_dir, "metrics.csv")

    @property
    def _runid_path(self) -> str:
        return self.path + ".runid"

    def _sync_with_existing_file(self) -> None:
        """Adopt an existing file's columns and switch to append mode —
        but only when the file belongs to this run (runid sidecar
        matches).  A matching file means this logger is a pickled copy of
        the run's original (plugins/xla.py re-pickles the trainer per
        dispatch, e.g. fit then validate) and must append; a mismatched
        or missing sidecar means the file is a leftover from a previous
        run sharing the root dir and must be truncated, not extended.
        """
        if self._started:
            return
        if os.path.exists(self.path):
            try:
                with open(self._runid_path) as f:
                    owner = f.read().strip()
            except OSError:
                owner = None
            if owner != self._run_id:
                return  # stale file from another run: overwrite on write
            with open(self.path, newline="") as f:
                header = next(csv.reader(f), None)
            if header:
                self._fields.extend(
                    k for k in header if k not in self._fields)
                self._started = True

    def log_metrics(self, metrics: dict, step: int) -> None:
        row = {"step": int(step)}
        for k, v in metrics.items():
            try:
                row[k] = float(v)
            except (TypeError, ValueError):
                continue
        self._sync_with_existing_file()
        new_fields = [k for k in row if k not in self._fields]
        if new_fields:
            self._fields.extend(new_fields)
            # schema grew (rare; e.g. first val_* after an epoch): fold
            # the existing file into the new header.  Steady state is an
            # O(1)-memory append — no rows are retained in memory.
            self._rewrite_with_new_header()
        os.makedirs(self.log_dir, exist_ok=True)
        mode = "a" if self._started else "w"
        if mode == "w":
            # Invariant: a sidecar naming run R exists only while the
            # csv holds R's rows.  Unlink first, write the csv, then
            # write the sidecar atomically — a crash anywhere in the
            # sequence leaves "no owner" (the next writer overwrites),
            # never a sidecar pointing at another run's rows (cross-run
            # mixing) and never a run truncating its own partial file.
            try:
                os.remove(self._runid_path)
            except FileNotFoundError:
                pass
        with open(self.path, mode, newline="") as f:
            writer = csv.DictWriter(f, fieldnames=self._fields, restval="")
            if mode == "w":
                writer.writeheader()
            writer.writerow(row)
        if mode == "w":
            fd, tmp = tempfile.mkstemp(dir=self.log_dir)
            with os.fdopen(fd, "w") as f:
                f.write(self._run_id)
            os.replace(tmp, self._runid_path)
        self._started = True

    def _rewrite_with_new_header(self) -> None:
        """Fold the existing file into the grown header.  Crash-safe:
        the re-headered copy is written to a temp file in the same
        directory and ``os.replace``d over the original, so a crash
        mid-rewrite leaves the old complete file, never a truncated
        ``metrics.csv``."""
        if not self._started or not os.path.exists(self.path):
            return
        with open(self.path, newline="") as f:
            reader = csv.DictReader(f)
            old_rows = list(reader)
        # another writer may have put columns there since this one
        # looked (two runs sharing a root dir): keep them, or their rows
        # could not be written back
        self._fields.extend(k for k in reader.fieldnames or ()
                            if k not in self._fields)
        fd, tmp = tempfile.mkstemp(dir=self.log_dir, suffix=".csv")
        try:
            with os.fdopen(fd, "w", newline="") as f:
                writer = csv.DictWriter(f, fieldnames=self._fields,
                                        restval="")
                writer.writeheader()
                for r in old_rows:
                    writer.writerow(r)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def finalize(self) -> None:
        """Everything is flushed on write; nothing buffered."""


def resolve_logger(logger, default_root_dir: str):
    """Trainer's ``logger=`` argument → a logger object or None.

    True → CSVLogger under the root dir; False/None → no logging;
    anything with ``log_metrics`` → used as-is.
    """
    if logger is True:
        return CSVLogger(default_root_dir)
    if not logger:
        return None
    if hasattr(logger, "log_metrics"):
        return logger
    raise TypeError(
        f"logger must be True/False or expose log_metrics(dict, step); "
        f"got {type(logger).__name__}")
