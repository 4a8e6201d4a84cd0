"""One journal under every ledger: per-rank persistence, stated once.

goodput, memwatch, dynamics, commswatch and the serving ledger each
keep a cumulative record that must outlive the process. Each builds ONE
`Journal` at import with what differs between them (file stem, schema
string, the flag naming its directory, and the callables that read and
seed the ledger) and binds its public `configure` / `flush` /
`load_journal` / ... names to it. What a journal does is the same for
all of them:

- the file is ``<dir>/<stem>.rank<k><ext>``, `k` being
  `monitor.trainer_rank()` at the moment of the write;
- `configure(dir)` turns persistence on and, with `resume`, seeds the
  ledger's cumulative base from an existing file, but only while the
  ledger is still pristine: steps already recorded (and possibly
  flushed) re-loaded as base would count twice. A torn or alien file is
  an absent one;
- `flush()` writes through `monitor.atomic_write_text` (temp +
  os.replace: a reader never sees a torn file), every `every` closed
  steps or ticks and once at exit; a no-op while unconfigured;
- a rank set after import (`monitor.set_trainer_rank`) drops the base
  resumed under the old identity and re-resumes under the new one;
- `load(path)` refuses a foreign schema; `load_all(dir, ranks)` reads
  every rank's file in name order, keeps this job's ranks, and skips
  what does not load.

This module imports `monitor` and no ledger; ledgers import it.
"""
from __future__ import annotations

import atexit
import glob
import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

from . import flags as _flags
from . import monitor as _monitor

__all__ = ["Journal", "rank_changed", "disable_persistence"]

_JOURNALS: List["Journal"] = []


def _encode(doc: Dict[str, Any]) -> str:
    return json.dumps(doc, indent=1)


class Journal:
    """The persistence of one ledger.

    ``ns`` is the ledger module's namespace (``globals()``): the
    journal's directory lives there as ``_JOURNAL_DIR`` (None =
    unconfigured), where the ledger's own code and its tests' fixtures
    read and restore it. ``ledger`` is the object whose ``base``
    attribute holds the totals resumed from a prior incarnation (None =
    nothing resumed). ``snapshot()`` is the document a flush writes,
    ``unused()`` whether the ledger has recorded nothing in this
    process, ``merge(docs)`` folds several ranks' documents into the
    job-level view. ``dir_flag`` names the environment flag whose
    directory, when set, configures the journal as it is built (an
    unwritable directory leaves the accounting in-process only)."""

    def __init__(self, ns: Dict[str, Any], ledger: Any, stem: str,
                 schema: str, dir_flag: str,
                 snapshot: Callable[[], Dict[str, Any]],
                 unused: Callable[[], bool],
                 merge: Optional[Callable[[List[Dict[str, Any]]],
                                          Dict[str, Any]]] = None,
                 every: int = 50, ext: str = ".json",
                 encode: Callable[[Dict[str, Any]], str] = _encode,
                 decode: Callable[[str], Dict[str, Any]] = json.loads):
        self._ns, self.ledger = ns, ledger
        self.stem, self.ext, self.schema = stem, ext, schema
        self.snapshot, self.unused, self.merge = snapshot, unused, merge
        self.encode, self.decode = encode, decode
        self.every = max(1, int(every))
        self._since_flush = 0
        self.dir = None
        _JOURNALS.append(self)
        env_dir = _flags.env_flag(dir_flag)
        if env_dir:
            try:
                os.makedirs(env_dir, exist_ok=True)
                self.configure(env_dir)
            except OSError:
                pass

    @property
    def dir(self) -> Optional[str]:
        return self._ns["_JOURNAL_DIR"]

    @dir.setter
    def dir(self, value: Optional[str]) -> None:
        self._ns["_JOURNAL_DIR"] = value

    def path(self, dir: Optional[str] = None) -> str:
        return os.path.join(
            dir or self.dir or ".",
            f"{self.stem}.rank{_monitor.trainer_rank()}{self.ext}")

    def configure(self, dir: Optional[str] = None,
                  every: Optional[int] = None,
                  resume: bool = True) -> None:
        if dir:
            self.dir = dir
            if resume and self.ledger.base is None:
                self._resume()
        if every is not None:
            self.every = max(1, int(every))

    def _resume(self) -> None:
        """Seed the base from this rank's file, but only while the
        ledger is still pristine: steps already recorded (and possibly
        flushed) re-loaded as base would count twice."""
        if not self.unused():
            return
        try:
            self.ledger.base = self.load(self.path())
        except (OSError, ValueError):
            self.ledger.base = None  # absent, torn or alien: start fresh

    def disable_persistence(self) -> None:
        """Drop persistence for THIS process (every later flush, the one
        at exit included, is a no-op): a supervisor that inherited a
        rank's environment must never clobber that rank's journal."""
        self.dir = None

    def rank_changed(self) -> None:
        if self.dir is None:
            return
        self.ledger.base = None  # it was resumed under the old identity
        self._resume()

    def reset(self) -> None:
        """The ledger was reset: the flush cadence starts over."""
        self._since_flush = 0

    def flush_if_due(self) -> None:
        """Count one closed step or tick and flush on every `every`-th.
        A full disk must not kill the loop that closed it."""
        if self.dir is None:
            return
        self._since_flush += 1
        if self._since_flush >= self.every:
            self._since_flush = 0
            try:
                self.flush()
            except OSError:
                pass

    def flush(self, path: Optional[str] = None) -> Optional[str]:
        """Write the journal atomically; returns its path. No-op (None)
        when persistence is unconfigured and no path is given."""
        if path is None:
            if self.dir is None:
                return None
            path = self.path()
        return _monitor.atomic_write_text(path,
                                          self.encode(self.snapshot()))

    def load(self, path: str) -> Dict[str, Any]:
        with open(path) as f:
            text = f.read()
        try:
            doc = self.decode(text)
        except ValueError as e:
            raise ValueError(f"{path}: {e}") from e
        if doc.get("schema") != self.schema:
            raise ValueError(f"{path}: not a {self.stem} journal (schema "
                             f"{doc.get('schema')!r})")
        return doc

    def load_all(self, dir: str,
                 ranks: Optional[Sequence[int]] = None
                 ) -> List[Dict[str, Any]]:
        """Every rank's journal in `dir`, in file-name order. `ranks`
        limits them to this job's membership, so journals an earlier,
        larger run left in the directory do not skew the merge."""
        want = None if ranks is None else {int(r) for r in ranks}
        docs = []
        pattern = os.path.join(dir, f"{self.stem}.rank*{self.ext}")
        for path in sorted(glob.glob(pattern)):
            try:
                doc = self.load(path)
            except (OSError, ValueError):
                continue  # a torn file cannot happen (atomic), an alien can
            if want is None or int(doc.get("rank", -1)) in want:
                docs.append(doc)
        return docs

    def load_merged(self, dir: str,
                    ranks: Optional[Sequence[int]] = None
                    ) -> Optional[Dict[str, Any]]:
        """`merge` over `load_all`: the job-level view launch.py prints
        at teardown and obs_report renders; None when nothing loads."""
        docs = self.load_all(dir, ranks)
        return self.merge(docs) if docs else None


def rank_changed() -> None:
    for j in _JOURNALS:
        j.rank_changed()


def disable_persistence() -> None:
    for j in _JOURNALS:
        j.disable_persistence()


def _flush_at_exit() -> None:
    for j in _JOURNALS:
        try:
            j.flush()
        except OSError:
            pass


_monitor.on_rank_change(rank_changed)
atexit.register(_flush_at_exit)
