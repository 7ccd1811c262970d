"""Dataset facade: InMemoryDataset / QueueDataset + DatasetFactory.

Reference: python/paddle/fluid/dataset.py (DatasetFactory:30,
InMemoryDataset:432 with load_into_memory/local_shuffle/global_shuffle,
QueueDataset:700) backed by the C++ MultiSlotDataset + DataFeed pipeline
(framework/data_set.h:88-108, a multi-threaded file-parsing service feeding
Hogwild workers).

TPU-native: the C++ service collapses into host-side numpy. Files are parsed
on load (text lines -> per-var columns), shuffles are host permutations --
``global_shuffle`` seeds identically on every host and each host keeps its
row stripe, which IS the reference's cross-trainer shuffle without the RPC
shuffle service. ``Executor.train_from_dataset`` then drives the standard
executor loop over the materialized batches.

Line format (the reference's MultiSlot text format, simplified): one sample
per line, slots separated by ``;``, values space-separated within a slot,
ordered as ``set_use_var``. Override with ``set_parse_fn(line) -> tuple``.
"""
from __future__ import annotations

import os
from typing import Callable, List, Optional

import numpy as np

from .observability import timeline as _timeline

BAD_SAMPLE_POLICIES = ("raise", "quarantine")
MISSING_FILE_POLICIES = ("raise", "skip")


class PoisonFeed(RuntimeError):
    """The quarantined-sample rate crossed the configured ceiling: the
    feed itself is corrupt (schema drift, upstream breakage), and silently
    training on whatever still parses would be worse than stopping.
    Raised typed by the shared ``on_bad_sample='quarantine'`` path
    (finite datasets here and ``paddle_tpu.data.StreamingDataset``)."""

    def __init__(self, msg: str, quarantined: int = 0, total: int = 0):
        super().__init__(msg)
        self.quarantined = quarantined
        self.total = total


class DeadLetterWriter:
    """Append-only JSONL sink for quarantined records: one line per
    poison sample carrying the source attribution (``where`` =
    ``file:line`` or ``source:position``), the failure reason, and the
    offending text (truncated).  Opened lazily on the first quarantine,
    flushed per write (a crashed run must not lose the evidence).
    Deduplicated by position -- a multi-epoch run re-parsing the same
    file, or a resume replaying the torn window past the last committed
    watermark, records each poison line ONCE (existing entries are
    re-read on open so dedup survives process restarts)."""

    MAX_TEXT = 512

    def __init__(self, path: str):
        self.path = path
        self._f = None
        self._seen = None   # where-keys already recorded (lazy)

    def write(self, where: str, reason: str, error: str, text: str) -> bool:
        """Record one poison line; returns False (and writes nothing) if
        this position was already dead-lettered."""
        import json
        if self._f is None:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._seen = set()
            if os.path.exists(self.path):
                try:
                    with open(self.path) as f:
                        for ln in f:
                            if ln.strip():
                                self._seen.add(
                                    json.loads(ln).get("where"))
                except (OSError, ValueError):
                    pass   # unreadable prior entries: record anew
            self._f = open(self.path, "a")
        if where in self._seen:
            return False
        self._seen.add(where)
        self._f.write(json.dumps(
            {"where": where, "reason": reason, "error": str(error)[:200],
             "line": str(text)[:self.MAX_TEXT]}, sort_keys=True) + "\n")
        self._f.flush()
        return True

    def close(self):
        if self._f is not None:
            self._f.close()
            self._f = None
            self._seen = None


class DatasetBase:
    def __init__(self):
        self.batch_size = 1
        self.use_vars = []
        self.filelist: List[str] = []
        self.thread_num = 1
        self.drop_last = False
        self.on_missing_file = "raise"   # or "skip" (journals the skip)
        self._parse_fn: Optional[Callable] = None
        self._samples = None     # row list of tuples OR columnar matrices
        self._perm = None        # shuffle permutation (a view, not a copy)
        self._stripe = None      # (rank, world) view set by global_shuffle
        self._epoch_seed = 0
        # poison-record policy (shared with paddle_tpu.data streaming):
        # "raise" (default, the historical behavior) or "quarantine"
        self._bad_policy = "raise"
        self._dead_letter: Optional[DeadLetterWriter] = None
        self._max_poison_rate: Optional[float] = None
        self._poison_floor = 20          # min samples before the ceiling arms
        # ceiling window (reset per load/epoch: the ceiling asks "is the
        # feed corrupt NOW", so a past burst must not poison the ratio of
        # a later pass) vs _quarantined, the CUMULATIVE dead-letter count
        # that rides the streaming watermark
        self._parse_total = 0            # counted only under quarantine
        self._rate_quarantined = 0
        self._quarantined = 0

    # -- reference config surface ------------------------------------------------------
    def set_batch_size(self, batch_size):
        self.batch_size = int(batch_size)

    def set_thread(self, thread_num):
        self.thread_num = int(thread_num)   # parity; parsing is vectorized

    def set_use_var(self, var_list):
        self.use_vars = list(var_list)

    def set_filelist(self, filelist):
        self.filelist = list(filelist)

    def set_pipe_command(self, pipe_command):
        import warnings
        warnings.warn("paddle_tpu Dataset: pipe_command (a subprocess parser) "
                      "is replaced by set_parse_fn(line)->tuple", UserWarning)

    def set_hdfs_config(self, fs_name, fs_ugi):
        raise NotImplementedError("HDFS IO: mount the data locally; "
                                  "SCOPE.md PS/CTR row")

    def set_parse_fn(self, fn):
        """TPU extension: fn(line:str) -> tuple of arrays/scalars per use_var."""
        self._parse_fn = fn

    def set_missing_file_policy(self, policy: str):
        """``"raise"`` (default): a missing file in the filelist aborts the
        load (the historical behavior).  ``"skip"``: the file is skipped,
        journaled as a ``source_skipped`` event and counted in
        ``sources_skipped_total`` -- a production feed where one shard
        lagging the publisher must not abort the whole multi-file load."""
        if policy not in MISSING_FILE_POLICIES:
            raise ValueError(f"on_missing_file must be one of "
                             f"{MISSING_FILE_POLICIES}, got {policy!r}")
        self.on_missing_file = policy

    def set_bad_sample_policy(self, policy: str = "quarantine",
                              dead_letter_path: Optional[str] = None,
                              max_poison_rate: Optional[float] = None,
                              poison_floor: int = 20):
        """``"raise"`` (default): a malformed line aborts with a ValueError
        carrying the source position.  ``"quarantine"``: the line is
        appended to the dead-letter file (``dead_letter_path``, default
        ``paddle_tpu_dead_letters.jsonl``) with source attribution,
        counted in ``samples_quarantined_total{reason}``, and skipped --
        unless the quarantine rate crosses ``max_poison_rate`` (checked
        once at least ``poison_floor`` samples were parsed), which raises
        a typed :class:`PoisonFeed` instead of silently training on a
        corrupt feed."""
        if policy not in BAD_SAMPLE_POLICIES:
            raise ValueError(f"on_bad_sample must be one of "
                             f"{BAD_SAMPLE_POLICIES}, got {policy!r}")
        self._bad_policy = policy
        if policy == "quarantine":
            if self._dead_letter is not None:   # re-arm: no fd leak
                self._dead_letter.close()
            self._dead_letter = DeadLetterWriter(
                dead_letter_path or "paddle_tpu_dead_letters.jsonl")
            self._max_poison_rate = (None if max_poison_rate is None
                                     else float(max_poison_rate))
            self._poison_floor = int(poison_floor)
        else:
            if self._dead_letter is not None:
                self._dead_letter.close()
            self._dead_letter = None
            self._max_poison_rate = None

    # -- parsing -----------------------------------------------------------------------
    def _parse_line(self, line, where: Optional[str] = None):
        if self._parse_fn is not None:
            return tuple(self._parse_fn(line))
        slots = line.strip().split(";")
        if len(slots) != len(self.use_vars):
            at = f" at {where}" if where else ""
            raise ValueError(
                f"line{at} has {len(slots)} slots but set_use_var lists "
                f"{len(self.use_vars)} vars (separate slots with ';' or use "
                f"set_parse_fn)")
        out = []
        for s, v in zip(slots, self.use_vars):
            dt = v.dtype if v.dtype != "bfloat16" else "float32"
            vals = s.split()
            try:
                out.append(np.asarray(vals, dtype=np.dtype(dt))
                           if vals else np.zeros((0,), dt))
            except ValueError as e:
                at = f" at {where}" if where else ""
                raise ValueError(
                    f"slot for var {v.name!r}{at} does not parse as "
                    f"{dt}: {e}") from e
        return tuple(out)

    def _parse_guarded(self, line, where: Optional[str] = None):
        """One line through :meth:`_parse_line` under the bad-sample
        policy: returns the parsed tuple, or None when the line was
        quarantined (``on_bad_sample='quarantine'``).  The default
        ``raise`` path adds no try/except on top of the plain parse."""
        if self._bad_policy == "raise":
            return self._parse_line(line, where=where)
        self._parse_total += 1
        try:
            return self._parse_line(line, where=where)
        except PoisonFeed:
            raise
        except Exception as e:  # noqa: BLE001 -- every parse failure
            self._quarantine(line, where, e)
            return None

    def _quarantine(self, line, where, err):
        """Dead-letter one malformed line (counter + journal + JSONL
        record with source attribution), then enforce the poison-rate
        ceiling."""
        reason = ("slot_count" if "slots but set_use_var" in str(err)
                  else "parse_error")
        self._quarantined += 1
        self._rate_quarantined += 1
        # counter/journal only on a NEW position: a re-parse (another
        # epoch, a resumed torn window) must not inflate the series --
        # the ceiling's _quarantined/_parse_total pair still counts per
        # parse so the rate stays consistent within an epoch
        if self._dead_letter.write(where or "?", reason, err, line):
            from .observability import journal as _journal
            from .observability.metrics import REGISTRY as _OBS
            _OBS.counter("samples_quarantined_total",
                         "malformed samples dead-lettered by the "
                         "quarantine policy, by reason",
                         reason=reason).inc()
            _journal.emit({"event": "sample_quarantined", "where": where,
                           "reason": reason, "error": str(err)[:120],
                           "dead_letter": self._dead_letter.path})
        if (self._max_poison_rate is not None and
                self._parse_total >= self._poison_floor and
                self._rate_quarantined / self._parse_total >
                self._max_poison_rate):
            raise PoisonFeed(
                f"poison-record rate {self._rate_quarantined}/"
                f"{self._parse_total} = "
                f"{self._rate_quarantined / self._parse_total:.1%} exceeds "
                f"the {self._max_poison_rate:.1%} ceiling (last offender "
                f"{where}); the feed looks corrupt -- refusing to keep "
                f"training on it (dead letters: {self._dead_letter.path})",
                quarantined=self._rate_quarantined,
                total=self._parse_total)

    def _reset_poison_window(self):
        """New load/epoch: the poison-rate ceiling judges THIS pass."""
        self._parse_total = 0
        self._rate_quarantined = 0

    def _missing_file(self, path) -> bool:
        """Missing-file policy: True = skip this path (journaled), else
        raise the historical FileNotFoundError."""
        if self.on_missing_file != "skip":
            raise FileNotFoundError(f"dataset file {path!r} not found")
        from .observability import journal as _journal
        from .observability.metrics import REGISTRY as _OBS
        _OBS.counter("sources_skipped_total",
                     "dataset files skipped by on_missing_file=skip").inc()
        _journal.emit({"event": "source_skipped", "file": str(path)})
        return True

    def _read_files(self):
        """Returns either columnar matrices (native C++ parse -- one
        contiguous [N, width] array per slot, no per-row object churn) or a
        row list of tuples (Python fallback). Both shapes are understood by
        _iter_batches and the shuffles (which permute an index array)."""
        self._reset_poison_window()
        col_parts: Optional[List[List[np.ndarray]]] = None
        samples = []
        for path in self.filelist:
            if not os.path.exists(path):
                if self._missing_file(path):
                    continue
            parsed, columnar = self._parse_file(path)
            if columnar and not samples:
                if col_parts is None:
                    col_parts = [[] for _ in parsed]
                for parts, c in zip(col_parts, parsed):
                    parts.append(c)
                continue
            if columnar:                # mixed native/python files: demote
                samples.extend(zip(*[list(c) for c in parsed]))
                continue
            if col_parts is not None:   # demote earlier columnar reads
                cols = [np.concatenate(p) for p in col_parts]
                samples.extend(zip(*[list(c) for c in cols]))
                col_parts = None
            samples.extend(parsed)
        if col_parts is not None and not samples:
            return [np.concatenate(p) for p in col_parts]
        return samples

    def _parse_file(self, path):
        """One file read, under one ``parse_file`` span: ``(columns, True)``
        from the native parser where the file qualifies, else ``(rows,
        False)`` from the Python line parser under the bad-sample policy."""
        with _timeline.phase("parse_file", cat="dataset",
                             bytes=os.path.getsize(path)):
            native = self._read_native(path)
            if native is not None:
                _timeline.annotate(rows=int(native[0].shape[0]), native=True)
                return native, True
            rows = []
            with open(path) as f:
                for ln, line in enumerate(f, 1):
                    if line.strip():
                        s = self._parse_guarded(line, where=f"{path}:{ln}")
                        if s is not None:
                            rows.append(s)
            _timeline.annotate(rows=len(rows), native=False)
            return rows, False

    def _read_native(self, path):
        """Multithreaded C++ slot parser (native/fast_parser.cpp, the
        data_feed.cc analog); None -> fall back to the Python line parser.
        Only the default rectangular slot format qualifies, and integer
        slots must round-trip float32 exactly (|v| < 2^24, integral) --
        hashed CTR ids beyond that fall back to the exact Python parse."""
        if self._parse_fn is not None or not self.use_vars:
            return None
        from . import native
        if not native.available():
            return None
        try:
            rows, cols = native.parse_slot_file(path, len(self.use_vars),
                                                n_threads=self.thread_num)
        except ValueError:
            return None   # ragged/typed lines: Python parser handles or errors
        typed = []
        for c, v in zip(cols, self.use_vars):
            dt = v.dtype if v.dtype != "bfloat16" else "float32"
            if np.issubdtype(np.dtype(dt), np.integer):
                if (np.abs(c) >= 2 ** 24).any() or (c != np.floor(c)).any():
                    return None   # float32 can't represent these ids exactly
                c = c.astype(np.dtype(dt))
            elif dt != "float32":
                c = c.astype(np.dtype(dt))
            typed.append(c)
        return typed

    @staticmethod
    def _is_columnar(samples):
        return (isinstance(samples, list) and samples and
                isinstance(samples[0], np.ndarray) and samples[0].ndim == 2)

    # -- iteration (used by Executor.train_from_dataset) -------------------------------
    def _n_samples(self, samples):
        return samples[0].shape[0] if self._is_columnar(samples) \
            else len(samples)

    def _iter_batches(self):
        samples = self._samples if self._samples is not None \
            else self._read_files()
        columnar = self._is_columnar(samples)
        idx = self._perm if getattr(self, "_perm", None) is not None \
            else np.arange(self._n_samples(samples))
        if self._stripe is not None:
            r, w = self._stripe
            idx = idx[r::w]
        names = [v.name for v in self.use_vars]
        bs = self.batch_size
        n = len(idx)
        if n == 0 or (self.drop_last and n < bs):
            import warnings
            warnings.warn(
                f"Dataset yields no batches: {n} samples on this "
                f"host vs batch_size={bs}", UserWarning)
            return
        for i in range(0, n, bs):
            take = idx[i:i + bs]
            if len(take) < bs and self.drop_last:
                return
            if columnar:
                yield {nm: c[take] for nm, c in zip(names, samples)}
            else:
                cols = list(zip(*[samples[j] for j in take]))
                yield {nm: np.stack([np.asarray(x) for x in c])
                       for nm, c in zip(names, cols)}


class InMemoryDataset(DatasetBase):
    """Reference dataset.py:432."""

    def load_into_memory(self):
        self._samples = self._read_files()

    def preload_into_memory(self, thread_num=None):
        self.load_into_memory()

    def wait_preload_done(self):
        return None

    def release_memory(self):
        self._samples = None
        self._perm = None
        self._stripe = None

    def get_memory_data_size(self, fleet=None):
        return 0 if self._samples is None else self._n_samples(self._samples)

    def get_shuffle_data_size(self, fleet=None):
        return self.get_memory_data_size(fleet)

    def local_shuffle(self):
        """Shuffles are index permutations -- the (possibly columnar) data
        never moves, so native-parsed matrices stay contiguous."""
        if self._samples is None:
            raise RuntimeError("call load_into_memory() first")
        rng = np.random.RandomState(self._epoch_seed)
        self._epoch_seed += 1
        self._perm = rng.permutation(self._n_samples(self._samples))

    def global_shuffle(self, fleet=None, thread_num=12):
        """Cross-trainer shuffle: every host applies the IDENTICAL seeded
        permutation, then keeps its row stripe -- equivalent to the
        reference's RPC shuffle service, no service. Both the permutation
        and the stripe are VIEWS applied at batch time, so repeated calls
        (one per epoch) reshuffle the whole dataset instead of
        geometrically shrinking the stripe."""
        if self._samples is None:
            raise RuntimeError("call load_into_memory() first")
        rng = np.random.RandomState(1000 + self._epoch_seed)
        self._epoch_seed += 1
        self._perm = rng.permutation(self._n_samples(self._samples))
        from .parallel import env as penv
        w, r = penv.get_world_size(), penv.get_rank()
        self._stripe = (r, w) if w > 1 else None


class QueueDataset(DatasetBase):
    """Reference dataset.py:700: streaming variant (no load_into_memory).

    _iter_batches really streams: each file is parsed as it is reached and
    its batches yielded immediately, so the executor's prefetch thread
    (core/executor.py:_prefetch_batches) overlaps file k+1's parse with
    file k's device steps -- the reference QueueDataset's whole purpose
    (data_feed.cc MultiSlotDataFeed queues). Row remainders carry across
    file boundaries so batching matches the eager path exactly.
    """

    def local_shuffle(self):
        raise ValueError("QueueDataset streams files; use InMemoryDataset "
                         "for shuffling (reference raises the same)")

    def global_shuffle(self, fleet=None):
        raise ValueError("QueueDataset streams files; use InMemoryDataset")

    def _iter_batches(self):
        if self._samples is not None:   # pre-loaded (tests): eager path
            yield from DatasetBase._iter_batches(self)
            return
        self._reset_poison_window()
        names = [v.name for v in self.use_vars]
        bs = self.batch_size
        stripe = self._stripe
        row_base = 0                      # global row counter for striping
        rows_kept = 0                     # post-stripe rows on this host
        pend: Optional[List[np.ndarray]] = None   # carried columnar rows
        pend_rows: list = []                      # carried python rows
        columnar_mode = None

        def flush(cols_or_rows, columnar, final=False):
            nonlocal pend, pend_rows
            if columnar:
                cols = cols_or_rows
                if pend is not None:
                    cols = [np.concatenate([p, c])
                            for p, c in zip(pend, cols)]
                n = cols[0].shape[0]
                stop = n if final else (n // bs) * bs
                for i in range(0, stop, bs):
                    if stop - i < bs and self.drop_last:
                        break
                    yield {nm: c[i:i + bs] for nm, c in zip(names, cols)}
                pend = None if final else [c[stop:] for c in cols]
            else:
                rows = pend_rows + cols_or_rows
                stop = len(rows) if final else (len(rows) // bs) * bs
                for i in range(0, stop, bs):
                    if stop - i < bs and self.drop_last:
                        break
                    batch = rows[i:i + bs]
                    cols = list(zip(*batch))
                    yield {nm: np.stack([np.asarray(x) for x in c])
                           for nm, c in zip(names, cols)}
                pend_rows = [] if final else rows[stop:]

        n_yielded = 0

        def counting(gen):
            nonlocal n_yielded
            for b in gen:
                n_yielded += 1
                yield b

        for path in self.filelist:
            if not os.path.exists(path):
                if self._missing_file(path):
                    continue
            cols, columnar = self._parse_file(path)
            if columnar_mode is None:
                columnar_mode = columnar
            elif columnar_mode != columnar:
                # mixed native/python files: demote the carried columnar
                # remainder to rows so batching stays exact
                if columnar and not columnar_mode:
                    cols = list(zip(*[list(c) for c in cols]))
                    columnar = False
                else:
                    if pend is not None:
                        pend_rows = list(zip(*[list(c) for c in pend]))
                        pend = None
                    columnar_mode = False
            n = (cols[0].shape[0] if columnar else len(cols))
            if stripe is not None:
                r, w = stripe
                keep = np.arange(n)[(row_base + np.arange(n)) % w == r]
                cols = ([c[keep] for c in cols] if columnar
                        else [cols[int(k)] for k in keep])
                rows_kept += len(keep)
            else:
                rows_kept += n
            row_base += n
            yield from counting(flush(cols, columnar_mode, final=False))
        # ONE final flush of the carried remainder after the loop -- it
        # owes its partial batch whether the last file streamed, was
        # skipped by on_missing_file, or the filelist was empty
        if pend is not None:
            yield from counting(flush([c[:0] for c in pend], True,
                                      final=True))
        elif pend_rows:
            yield from counting(flush([], False, final=True))
        if n_yielded == 0:
            import warnings
            warnings.warn(
                f"Dataset yields no batches: {rows_kept} samples on this "
                f"host vs batch_size={bs}", UserWarning)


class DatasetFactory:
    """Reference dataset.py:30."""

    def create_dataset(self, datafeed_class="QueueDataset"):
        if datafeed_class == "InMemoryDataset":
            return InMemoryDataset()
        if datafeed_class == "QueueDataset":
            return QueueDataset()
        if datafeed_class == "StreamingDataset":
            # lazy: the streaming data plane (reader threads, buffers) is
            # paid for only when asked for (zero-overhead guard)
            from .data import StreamingDataset
            return StreamingDataset()
        raise ValueError(f"unknown dataset class {datafeed_class!r}")
