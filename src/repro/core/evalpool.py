"""Evaluation pool: decouples the GA loop from fitness measurement.

The paper's search cost is dominated by verification-environment
measurements (§5.2: caching fitness for recurring gene patterns is what
made the 7-hour budget feasible), and the mixed-destination follow-up
(arXiv:2011.12431) searches several backends at once, multiplying the
measurements per generation. This module scales that bottleneck three
ways, without changing GA semantics:

- **dedup** — identical gene patterns inside one generation are measured
  once (roulette selection re-picks strong parents, so duplicates are
  common in late generations);
- **persistent fitness cache** — measurements are appended to an on-disk
  JSONL file keyed by (evaluator fingerprint, genome), so a killed search
  resumes without re-measuring anything it already paid for, and repeated
  calibration sweeps share measurements across processes;
- **concurrent evaluation** — the unique, uncached individuals of a
  generation run on a thread (or process) pool with the paper's
  per-individual timeout -> penalty semantics preserved, or through an
  evaluator-provided ``evaluate_batch`` (the ``CompiledEvaluator``'s
  batched AOT-compile path).

An exception from the evaluator scores as the penalty (the paper's
compile-error analogue) unless the evaluator sets
``measures_device = True`` (``MeasuredEvaluator`` does): then it
propagates and fails the run — a crashed clock says nothing about the
placement, and a penalty would hide the failure behind an all-host win.
Such an evaluator also runs only in-line — one measurement at a time, in
this process: the chip belongs to the process that touched JAX first, so
a child would find it held, and two clocks on it would time each other.

Determinism: the GA's RNG stream never depends on evaluation order or
worker count, and results are reduced back into population order, so a
fixed seed produces the same best individual at pool size 1 and N.

Cache file format (JSONL, one record per line, append-only)::

    {"v": 1, "fp": "<evaluator fingerprint>", "genes": "0110...",
     "t": <measured seconds, float>, "penalized": <bool>}

- ``v``        format version (this module writes 1, skips others);
- ``fp``       evaluator fingerprint — configuration string such as
               ``miniapp:himeno:bulk:staged:quadro-p4000``; entries whose
               fingerprint differs from the pool's are ignored, so one
               file can serve many searches;
- ``genes``    the genome's cache key. By default the gene digits as a
               string (``"0110..."``; k-ary genomes use digits up to
               k-1). An evaluator may provide ``cache_key(genes) -> str``
               to canonicalize the key — the mixed-destination evaluator
               maps destination *indices* (subset-relative) to destination
               *names*, so searches over different destination subsets
               share measurements for placements they both contain;
- ``t``        the time fed back to the GA (post-penalty, seconds);
- ``penalized`` whether ``t`` is the timeout/failure penalty rather than
               a real measurement. Penalized records are written (for
               telemetry/audit) but NOT replayed by ``load``: a timeout
               may be transient and the penalty constant may differ
               between runs, so resumed searches re-measure those
               genomes instead of inheriting a poisoned value.

Truncated/corrupt trailing lines (a killed writer) are skipped on load.
Appends are **multi-owner safe**: every record is written as ONE
``os.write`` to an ``O_APPEND`` descriptor under an advisory ``flock``,
so concurrent FitnessCache objects over the same path — two pools in one
process, or two service workers in different processes — never interleave
partial lines. Concurrent readers see a prefix of the log and a resumed
search re-reads its own history. Use :meth:`FitnessCache.load` /
:meth:`FitnessCache.flush_sync` for explicit control.

Shared (serving-side) use goes through :class:`EvalBroker`: one JSONL
store path handing out refcounted per-fingerprint cache views, so many
concurrent Offloaders share one in-memory cache per evaluator family and
a stage ``close()`` never yanks the store out from under a sibling
search (docs/serving.md).
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

try:  # advisory inter-process append lock; absent on some platforms
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX fallback
    fcntl = None

Genes = Tuple[int, ...]

_CACHE_VERSION = 1


def genes_key(genes: Sequence[int]) -> str:
    """Genome -> stable string key ('0110...')."""
    return "".join(str(int(g)) for g in genes)


def _atomic_append(fd: int, data: bytes) -> None:
    """Append one whole record to an ``O_APPEND`` descriptor without
    interleaving with other writers: a single ``os.write`` under an
    advisory exclusive ``flock`` (the lock also covers the rare partial
    write a signal could split)."""
    if fcntl is not None:
        fcntl.flock(fd, fcntl.LOCK_EX)
    try:
        while data:
            n = os.write(fd, data)
            data = data[n:]
    finally:
        if fcntl is not None:
            fcntl.flock(fd, fcntl.LOCK_UN)


def evaluator_fingerprint(evaluate: Callable) -> str:
    """Configuration fingerprint for an evaluator callable.

    Evaluators must provide ``fingerprint()`` (every shipped evaluator
    does). The fingerprint keys the persistent cache, so two
    differently-configured evaluators never share measurements — which
    is exactly why a name-based fallback is refused: two instances of
    the same evaluator class with different constants would share a
    qualified name, and their cached measurements would silently
    cross-contaminate.
    """
    fp = getattr(evaluate, "fingerprint", None)
    if callable(fp):
        return str(fp())
    name = getattr(evaluate, "__qualname__", None) or type(evaluate).__name__
    mod = getattr(evaluate, "__module__", "")
    raise TypeError(
        f"evaluator {mod}.{name} has no fingerprint(); refusing to key "
        "the persistent fitness cache on its name alone (two "
        "differently-configured instances would share cached "
        "measurements) — give it a fingerprint() method"
    )


class FitnessCache:
    """Genome -> measured seconds, optionally persisted as JSONL.

    With ``path=None`` this is a plain in-memory dict (the GA's original
    §5.2 cache). With a path, every ``put`` appends one JSON line and the
    constructor replays the file, so a killed search resumes warm.

    ``key_fn`` maps a genome to its cache-key string (default:
    :func:`genes_key`, the digit string). :class:`EvalPool` swaps in the
    evaluator's ``cache_key`` when it provides one, so callers normally
    construct the cache with just ``(path, fingerprint)``.

    **Multi-owner semantics.** Appends go through a single ``os.write``
    on an ``O_APPEND`` descriptor under an advisory ``flock``, so several
    cache objects over one path (in one process or many) never tear each
    other's lines. ``close()`` is refcounted: each :meth:`retain` call
    adds an owner and each ``close()`` releases one; the descriptor
    closes when the last owner leaves, so a pipeline stage closing its
    view of a shared store cannot double-close or strand a sibling
    search mid-write. Constructing the object counts as the first owner,
    which keeps single-owner callers exactly as before.
    """

    def __init__(
        self,
        path: Optional[str] = None,
        fingerprint: str = "",
        key_fn: Callable[[Sequence[int]], str] = genes_key,
    ):
        self.path = path
        self.fingerprint = fingerprint
        self.key_fn = key_fn
        self._mem: Dict[str, float] = {}
        self._lock = threading.Lock()
        self._fd: Optional[int] = None
        self._refs = 1  # construction is the first ownership
        self.loaded = 0  # records replayed from disk at construction
        if path:
            self.load()
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._fd = os.open(
                path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644
            )

    def load(self) -> int:
        """(Re)read the JSONL file; skips foreign-fingerprint, foreign-
        version, and corrupt lines. Returns records absorbed."""
        if not self.path or not os.path.exists(self.path):
            return 0
        n = 0
        with open(self.path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except (json.JSONDecodeError, ValueError):
                    continue  # truncated trailing write from a killed run
                if not isinstance(rec, dict):
                    continue
                if rec.get("v") != _CACHE_VERSION:
                    continue
                if rec.get("fp") != self.fingerprint:
                    continue
                if rec.get("penalized"):
                    continue  # transient/param-dependent; re-measure
                genes, t = rec.get("genes"), rec.get("t")
                if not isinstance(genes, str) or not isinstance(
                    t, (int, float)
                ):
                    continue
                self._mem[genes] = float(t)
                n += 1
        self.loaded += n
        return n

    def __len__(self) -> int:
        return len(self._mem)

    def __contains__(self, genes: Sequence[int]) -> bool:
        return self.key_fn(genes) in self._mem

    def get(
        self, genes: Sequence[int], key: Optional[str] = None
    ) -> Optional[float]:
        """``key`` overrides ``key_fn`` for this lookup — the EvalPool
        passes its own evaluator-derived keys so one cache object can
        serve pools over different evaluators without being mutated."""
        return self._mem.get(key if key is not None else self.key_fn(genes))

    def put(
        self,
        genes: Sequence[int],
        t: float,
        penalized: bool = False,
        key: Optional[str] = None,
    ) -> None:
        key = key if key is not None else self.key_fn(genes)
        with self._lock:
            self._mem[key] = float(t)
            if self._fd is not None:
                rec = {
                    "v": _CACHE_VERSION,
                    "fp": self.fingerprint,
                    "genes": key,
                    "t": float(t),
                    "penalized": bool(penalized),
                }
                _atomic_append(
                    self._fd, (json.dumps(rec) + "\n").encode("utf-8")
                )

    def retain(self) -> "FitnessCache":
        """Register another owner; its ``close()`` is then a release,
        not a descriptor close. Returns self for chaining."""
        with self._lock:
            self._refs += 1
        return self

    def flush_sync(self) -> None:
        if self._fd is not None:
            os.fsync(self._fd)

    def close(self) -> None:
        """Release one ownership; the descriptor closes when the last
        owner leaves. Extra closes are no-ops (never double-close)."""
        with self._lock:
            if self._refs > 0:
                self._refs -= 1
            if self._refs == 0 and self._fd is not None:
                os.close(self._fd)
                self._fd = None

    def __enter__(self) -> "FitnessCache":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class EvalBroker:
    """One shared fitness-cache store multiplexed across concurrent
    searches — the serving layer's half of "one shared EvalPool".

    The broker owns a single JSONL store path and hands out one
    refcounted :class:`FitnessCache` view per evaluator fingerprint:

    - concurrent searches whose evaluators share a fingerprint (e.g.
      mixed-destination searches over different destination subsets of
      one machine — the fingerprint is subset-independent) share ONE
      in-memory view, so a measurement either of them pays is a hit for
      the other *immediately*, not only after a file re-read;
    - each view is retained per :meth:`open_cache` call, so a pipeline
      stage closing "its" cache merely releases its reference — the
      broker keeps every view alive (and its descriptor open) until
      :meth:`close`;
    - all views append to the same file through the cache's atomic
      O_APPEND writes, so searches in *other processes* sharing the
      store stay safe too, and a service restart replays everything.

    Worker budgeting stays with the callers (an :class:`EvalPool` per
    search, as ever); the serving layer bounds total measurement
    concurrency by admission (max in-flight jobs x per-job workers).
    """

    def __init__(self, path: str):
        self.path = path
        self._views: Dict[str, FitnessCache] = {}
        self._lock = threading.Lock()

    def open_cache(self, fingerprint: str) -> FitnessCache:
        """A retained cache view for this fingerprint; the caller's
        ``close()`` releases its reference only."""
        with self._lock:
            view = self._views.get(fingerprint)
            if view is None:
                view = FitnessCache(self.path, fingerprint=fingerprint)
                self._views[fingerprint] = view
        return view.retain()

    def stats(self) -> Dict[str, int]:
        """entries per open fingerprint view (observability)."""
        with self._lock:
            return {fp: len(v) for fp, v in self._views.items()}

    def close(self) -> None:
        """Release the broker's own reference on every view (views still
        retained by in-flight stages stay open until those release)."""
        with self._lock:
            views, self._views = list(self._views.values()), {}
        for v in views:
            v.close()

    def __enter__(self) -> "EvalBroker":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@dataclasses.dataclass
class GenTelemetry:
    """Per-generation search telemetry (emitted by evaluate_generation)."""

    submitted: int = 0  # individuals handed to the pool
    unique: int = 0  # distinct genomes after in-generation dedup
    cache_hits: int = 0  # dedup repeats + persistent/memory cache serves
    evaluated: int = 0  # fresh measurements actually run
    timeouts: int = 0  # measurements scored as the penalty
    wall_s: float = 0.0  # generation wall-clock (submit -> all reduced)
    # lane-seconds the pool's workers spent waiting rather than measuring
    # (generational path: the barrier stall behind the slowest lane;
    # steady-state path: lanes starved because the breeder fell behind)
    idle_s: float = 0.0

    @property
    def dedup_ratio(self) -> float:
        """Fraction of submissions that were in-generation repeats of
        another individual (a strict subset of what hit_rate counts)."""
        if self.submitted == 0:
            return 0.0
        return (self.submitted - self.unique) / self.submitted

    @property
    def hit_rate(self) -> float:
        """Fraction of submissions answered without a fresh measurement
        (in-generation repeats + memory/persistent cache serves)."""
        if self.submitted == 0:
            return 0.0
        return self.cache_hits / self.submitted

    def row(self) -> Dict[str, float]:
        return {
            "submitted": self.submitted,
            "unique": self.unique,
            "cache_hits": self.cache_hits,
            "evaluated": self.evaluated,
            "timeouts": self.timeouts,
            "wall_s": round(self.wall_s, 4),
            # named *_wall_s on purpose: observability comparisons scrub
            # wall-clock-derived row keys by that suffix
            "idle_wall_s": round(self.idle_s, 4),
            "dedup_ratio": round(self.dedup_ratio, 4),
            "hit_rate": round(self.hit_rate, 4),
        }


# the long-form name the pipeline/trace observability layer uses for
# this record (persisted per generation in the search payload and
# carried on every per-generation trace event)
GenerationTelemetry = GenTelemetry


def _measures_device(evaluate: Callable) -> bool:
    return bool(getattr(evaluate, "measures_device", False))


def _timed_call(
    evaluate: Callable[[Genes], float], genes: Genes
) -> Tuple[float, float]:
    """(value, duration) for one measurement — module-level so the
    process executor can pickle it. The duration is the worker lane's
    busy time, the raw material for idle-lane attribution."""
    t0 = time.perf_counter()
    v = evaluate(genes)
    return float(v), time.perf_counter() - t0


def _run_with_executor(
    executor_kind: str,
    workers: int,
    evaluate: Callable[[Genes], float],
    genes_list: List[Genes],
    timeout_s: float,
) -> List[Tuple[float, bool, float]]:
    """Measure each genome; returns (raw seconds, timed_out, busy
    seconds) per genome — busy 0.0 for timeouts/crashes whose duration
    was never observed.

    Thread pools cannot kill a hung measurement, but a future that misses
    its deadline is *scored* as a timeout immediately (the straggler
    finishes in the background, exactly like the paper's verification
    machine finishing a run after the 3-minute cutoff already penalized
    it). Process pools get the same deadline semantics.
    """
    out: List[Tuple[float, bool, float]] = (
        [(float("inf"), True, 0.0)] * len(genes_list)
    )
    if executor_kind == "process":
        import multiprocessing as mp

        # spawn, not fork: the parent has usually initialized JAX/XLA
        # (runtime threads + locks), and forking that state can deadlock
        # the child mid-measurement. Spawn requires the evaluator to be
        # picklable. Never for work that needs the chip: the parent that
        # touched JAX holds it, so a child would fail or fall back.
        ex = cf.ProcessPoolExecutor(
            max_workers=max(1, workers), mp_context=mp.get_context("spawn")
        )
    else:
        ex = cf.ThreadPoolExecutor(max_workers=max(1, workers))
    try:
        t0 = time.monotonic()
        futs = {
            ex.submit(_timed_call, evaluate, g): i
            for i, g in enumerate(genes_list)
        }
        # every individual gets its full timeout; with w workers the batch
        # runs in ceil(n/w) waves, so the generation deadline is that many
        # timeouts out
        deadline = t0 + timeout_s * max(
            1, (len(genes_list) + workers - 1) // max(1, workers)
        )
        requeue: List[int] = []
        for fut in list(futs):
            i = futs[fut]
            try:
                remaining = max(0.0, deadline - time.monotonic())
                v, dur = fut.result(timeout=remaining)
                out[i] = (float(v), False, float(dur))
            except cf.TimeoutError:
                if fut.cancel():
                    # never started (earlier hangs held every worker):
                    # it used none of its budget, so it gets re-measured
                    # below instead of being penalized unmeasured
                    requeue.append(i)
                else:
                    out[i] = (float("inf"), True, 0.0)
            except Exception:  # measurement crash == compile error == penalty
                out[i] = (float("inf"), True, 0.0)
    finally:
        # don't block on hung stragglers mid-search: they are already
        # scored as penalties and their results discarded while the GA
        # moves on. LIMITATION: a worker that never returns still blocks
        # interpreter exit (concurrent.futures joins surviving workers
        # atexit), so an evaluator that can deadlock outright should
        # enforce its own hard timeout (subprocess + kill), as a real
        # verification harness does.
        ex.shutdown(wait=False, cancel_futures=True)
    if requeue:
        # fresh executor, fresh deadline — each requeued individual still
        # runs under timeout enforcement (never unbounded inline). Hangs
        # shrink the set every round, so this terminates.
        sub = _run_with_executor(
            executor_kind, workers, evaluate,
            [genes_list[i] for i in requeue], timeout_s,
        )
        for i, r in zip(requeue, sub):
            out[i] = r
    return out


class EvalPool:
    """Evaluates whole GA generations: dedup -> cache -> concurrent misses.

    Parameters
    ----------
    evaluate:
        ``genes -> seconds`` callable (any of the three core evaluators).
        If it exposes ``evaluate_batch(list_of_genes) -> list_of_seconds``
        and ``batch=True``, cache misses go through it in one call (the
        ``CompiledEvaluator`` uses this for its batched AOT-compile path).
    workers:
        Concurrent measurements for the executor path. 1 with the thread
        executor = serial in-line execution (no executor; byte-identical
        to the pre-pool GA loop, and what ``run_ga`` builds when no pool
        is passed). A process pool runs through the executor even at
        workers=1: the caller asked for child processes.
    executor:
        "thread" (default) or "process". Threads suit the analytic,
        compiled and measured evaluators (numpy/XLA release the GIL, and
        a measurement must run in the process that holds the chip);
        processes suit CPU-bound pure-Python evaluators that never touch
        JAX — and require picklable evaluators.
    cache:
        A :class:`FitnessCache`. Defaults to a fresh in-memory cache.
        If the evaluator provides ``cache_key(genes) -> str``, the POOL
        keys every lookup/store with it (the cache object itself is
        never mutated, so one cache can serve several pools) — this is
        how the mixed-destination evaluator canonicalizes subset-relative
        destination indices to destination names so different searches
        share measurements.
    """

    def __init__(
        self,
        evaluate: Callable[[Genes], float],
        workers: int = 1,
        executor: str = "thread",
        cache: Optional[FitnessCache] = None,
        batch: bool = True,
    ):
        if executor not in ("thread", "process"):
            raise ValueError(f"executor must be thread|process: {executor!r}")
        self.evaluate = evaluate
        self.workers = max(1, int(workers))
        if _measures_device(evaluate) and (executor, self.workers) != (
                "thread", 1):
            raise ValueError(
                "a device measurement runs one at a time, in the process "
                "that holds the chip; use executor='thread', workers=1"
            )
        self.executor = executor
        # a cache the pool built itself is closed by close(); a CALLER's
        # cache is left open — it may be serving other pools (the
        # advertised cross-subset sharing), and every put is flushed to
        # disk immediately so nothing is lost either way. Callers that
        # construct a persistent cache own its close().
        self._owns_cache = cache is None
        self.cache = cache if cache is not None else FitnessCache()
        ck = getattr(evaluate, "cache_key", None)
        self.key_fn: Callable[[Genes], str] = (
            ck if callable(ck) else self.cache.key_fn
        )
        self.batch = batch
        self.history: List[GenTelemetry] = []

    # -- single-genome path (kept for spot queries / penalty application) --

    def _penalize(
        self, t: float, timeout_s: float, penalty_time_s: float
    ) -> Tuple[float, bool]:
        ok = (
            t == t  # not NaN
            and t >= 0.0
            and t != float("inf")
            and t < timeout_s
        )
        return (t, False) if ok else (penalty_time_s, True)

    def evaluate_generation(
        self,
        population: Sequence[Genes],
        timeout_s: float,
        penalty_time_s: float,
    ) -> Tuple[List[float], GenTelemetry]:
        """Times for every individual, in population order, plus telemetry.

        Every returned time is post-penalty (the GA consumes it as-is).
        """
        t0 = time.monotonic()
        tel = GenTelemetry(submitted=len(population))
        pop = [tuple(int(g) for g in ind) for ind in population]

        # in-generation dedup + cache lookup, both on the CANONICAL key:
        # genomes that canonicalize identically (e.g. mixed-destination
        # placements that clamp to the same admissible plan) share one
        # measurement even within a generation
        keys = [self.key_fn(ind) for ind in pop]
        unique: Dict[str, Genes] = {}
        for ind, key in zip(pop, keys):
            if key not in unique:
                unique[key] = ind
        tel.unique = len(unique)

        times: Dict[str, float] = {}
        misses: List[Tuple[str, Genes]] = []
        for key, ind in unique.items():
            hit = self.cache.get(ind, key=key)
            if hit is not None:
                # re-validate against THIS run's params: a resumed search
                # may use a tighter timeout than the run that measured
                # the value, in which case the stored time must score as
                # the penalty now (the cache record itself is untouched)
                times[key] = self._penalize(hit, timeout_s, penalty_time_s)[0]
            else:
                misses.append((key, ind))
        # dedup repeats + cache serves both avoid a fresh measurement
        tel.cache_hits = (len(pop) - len(unique)) + (len(unique) - len(misses))
        tel.evaluated = len(misses)

        if misses:
            m0 = time.monotonic()
            raw, lanes = self._measure([ind for _, ind in misses], timeout_s)
            mwall = time.monotonic() - m0
            busy = sum(r[2] for r in raw)
            # barrier stall: lane-seconds held open past their last
            # measurement while the slowest lane finished the generation
            tel.idle_s = max(0.0, mwall * lanes - busy)
            for (key, ind), (t, timed_out, _dur) in zip(misses, raw):
                t, penalized = self._penalize(t, timeout_s, penalty_time_s)
                penalized = penalized or timed_out
                if penalized:
                    t = penalty_time_s
                    tel.timeouts += 1
                times[key] = t
                self.cache.put(ind, t, penalized=penalized, key=key)

        tel.wall_s = time.monotonic() - t0
        self.history.append(tel)
        return [times[key] for key in keys], tel

    def _measure(
        self, misses: List[Genes], timeout_s: float
    ) -> Tuple[List[Tuple[float, bool, float]], int]:
        """-> ((raw seconds, timed_out, busy seconds) per miss, lanes).

        ``lanes`` is the worker count the measurement actually occupied;
        the caller attributes ``wall * lanes - sum(busy)`` as idle time.
        """
        # NOTE: the batch path trusts the evaluator to bound its own time
        # (CompiledEvaluator treats a failed compile as inf itself); only
        # the executor path below enforces the wall-clock deadline. Pass
        # batch=False to force deadline enforcement for a batch-capable
        # evaluator.
        batch_fn = getattr(self.evaluate, "evaluate_batch", None)
        if self.batch and callable(batch_fn):
            try:
                b0 = time.perf_counter()
                vals = batch_fn(misses)
                per = (time.perf_counter() - b0) / max(1, len(vals))
                return [(float(t), False, per) for t in vals], 1
            except Exception:
                pass  # batch path degraded; fall through to point-wise
        # the inline shortcut (byte-identical to the pre-pool GA loop)
        # applies to THREAD pools only: a process pool was asked for
        # child processes, even at workers=1
        if self.workers == 1 and self.executor == "thread":
            out: List[Tuple[float, bool, float]] = []
            for g in misses:
                try:
                    v, dur = _timed_call(self.evaluate, g)
                    out.append((v, False, dur))
                except Exception:
                    if _measures_device(self.evaluate):
                        raise
                    out.append((float("inf"), True, 0.0))
            return out, 1
        raw = _run_with_executor(
            self.executor, self.workers, self.evaluate, misses, timeout_s
        )
        # tolerate 2-tuples from substituted executors (tests stub this
        # boundary); busy time simply goes unattributed
        norm = [
            (float(r[0]), bool(r[1]), float(r[2]) if len(r) > 2 else 0.0)
            for r in raw
        ]
        return norm, min(self.workers, len(misses)) or 1

    # -- aggregate telemetry ------------------------------------------------

    def totals(self) -> GenTelemetry:
        tot = GenTelemetry()
        for t in self.history:
            tot.submitted += t.submitted
            tot.unique += t.unique
            tot.cache_hits += t.cache_hits
            tot.evaluated += t.evaluated
            tot.timeouts += t.timeouts
            tot.wall_s += t.wall_s
            tot.idle_s += t.idle_s
        return tot

    def steady_session(
        self, timeout_s: float, penalty_time_s: float
    ) -> "SteadySession":
        """A :class:`SteadySession` over this pool's evaluator, cache,
        key function and worker budget (the steady-state GA's half of
        ``evaluate_generation``)."""
        return SteadySession(self, timeout_s, penalty_time_s)

    def close(self) -> None:
        if self._owns_cache:
            self.cache.close()

    def __enter__(self) -> "EvalPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class SteadySession:
    """Continuous evaluation without a generation barrier.

    The generational :meth:`EvalPool.evaluate_generation` holds every
    worker until the slowest measurement of the batch lands (the
    barrier-idle stall the telemetry's ``idle_s`` measures). A steady
    session instead keeps the lanes saturated: the caller ``submit``\\ s
    offspring whenever it has one and ``collect``\\ s finished
    ``(genes, seconds)`` results in completion order, one at a time.

    Semantics match the generational path exactly:

    - **dedup/cache** — submissions are canonicalized through the pool's
      ``key_fn``; persistent-cache hits are re-validated against THIS
      session's timeout (penalty re-applied if the stored time no longer
      fits) and resolve immediately; a submission whose key is already
      in flight never measures twice — it waits on the in-flight result;
    - **timeout -> penalty** — a measurement past ``timeout_s`` is scored
      ``penalty_time_s`` the moment its deadline passes (the straggler
      finishes in the background and its late result is discarded), and
      the penalized record is persisted exactly like the barrier path;
    - **telemetry** — the same :class:`GenTelemetry` counters, windowed:
      :meth:`cut` closes the current window, appends it to
      ``pool.history`` and starts the next, so a steady search still
      emits one telemetry row per generation-equivalent. Within every
      window ``submitted == evaluated + cache_hits`` holds (in-flight
      joins count as hits). ``idle_s`` here attributes *starvation*:
      lane-seconds workers sat free because the caller had nothing in
      flight to give them.

    Thread-safe; ``submit`` may be called from ``collect``'s thread or
    any other. With the pool's inline configuration (1 thread worker)
    submissions evaluate synchronously — byte-identical measurement
    order to the generational inline path.
    """

    def __init__(
        self, pool: EvalPool, timeout_s: float, penalty_time_s: float
    ):
        self.pool = pool
        self.timeout_s = float(timeout_s)
        self.penalty_time_s = float(penalty_time_s)
        self.tel = GenTelemetry()
        self._cond = threading.Condition()
        self._done: List[Tuple[Genes, float]] = []
        # key -> (first-submitted genes, duplicate waiters)
        self._pending: Dict[str, Tuple[Genes, List[Genes]]] = {}
        self._deadlines: Dict[str, float] = {}
        self._zombies: set = set()
        self._inflight = 0
        self._idle = 0.0
        self._seen: set = set()  # per-window unique keys
        self._t0 = time.monotonic()
        self._ex: Optional[cf.Executor] = None
        self._inline = pool.workers == 1 and pool.executor == "thread"

    @property
    def in_flight(self) -> int:
        with self._cond:
            return self._inflight

    def _executor(self) -> cf.Executor:
        if self._ex is None:
            if self.pool.executor == "process":
                import multiprocessing as mp

                self._ex = cf.ProcessPoolExecutor(
                    max_workers=self.pool.workers,
                    mp_context=mp.get_context("spawn"),
                )
            else:
                self._ex = cf.ThreadPoolExecutor(
                    max_workers=self.pool.workers
                )
        return self._ex

    def submit(self, genes: Sequence[int]) -> None:
        """Queue one individual; its result arrives via :meth:`collect`
        (immediately for cache hits, eventually otherwise)."""
        ind = tuple(int(g) for g in genes)
        key = self.pool.key_fn(ind)
        with self._cond:
            self.tel.submitted += 1
            hit = self.pool.cache.get(ind, key=key)
            if hit is not None:
                t = self.pool._penalize(
                    hit, self.timeout_s, self.penalty_time_s
                )[0]
                self.tel.cache_hits += 1
                if key not in self._seen:
                    self._seen.add(key)
                    self.tel.unique += 1
                self._done.append((ind, t))
                self._cond.notify_all()
                return
            if key in self._pending:
                # an identical genome is mid-measurement: join it
                self.tel.cache_hits += 1
                self._pending[key][1].append(ind)
                return
            if key not in self._seen:
                self._seen.add(key)
                self.tel.unique += 1
            self.tel.evaluated += 1
            self._pending[key] = (ind, [])
            self._deadlines[key] = time.monotonic() + self.timeout_s
            self._inflight += 1
        if self._inline:
            try:
                raw = float(self.pool.evaluate(ind))
            except Exception:
                if _measures_device(self.pool.evaluate):
                    raise
                raw = float("inf")
            self._resolve(key, raw)
        else:
            fut = self._executor().submit(
                _timed_call, self.pool.evaluate, ind
            )
            fut.add_done_callback(
                lambda f, k=key: self._on_future(k, f)
            )

    def _on_future(self, key: str, fut: "cf.Future") -> None:
        try:
            raw, _dur = fut.result()
        except Exception:
            raw = float("inf")
        self._resolve(key, float(raw))

    def _resolve(self, key: str, raw: float) -> None:
        t, penalized = self.pool._penalize(
            raw, self.timeout_s, self.penalty_time_s
        )
        with self._cond:
            if key in self._zombies:
                # already deadline-expired and scored as the penalty;
                # the late result is discarded, never double-counted
                self._zombies.discard(key)
                return
            ind, waiters = self._pending.pop(key)
            self._deadlines.pop(key, None)
            self._inflight -= 1
            if penalized:
                t = self.penalty_time_s
                self.tel.timeouts += 1
            self.pool.cache.put(ind, t, penalized=penalized, key=key)
            self._done.append((ind, t))
            for w in waiters:
                self._done.append((w, t))
            self._cond.notify_all()

    def collect(self) -> Tuple[Genes, float]:
        """Block for the next finished individual -> (genes, seconds).

        Results arrive in completion order, duplicates resolving with
        their measured twin. Raises ``RuntimeError`` if nothing is in
        flight and nothing is queued (a deadlocked caller bug)."""
        with self._cond:
            while not self._done:
                if self._inflight == 0:
                    raise RuntimeError(
                        "SteadySession.collect() with no submission in "
                        "flight"
                    )
                now = time.monotonic()
                expired = [
                    k for k, dl in self._deadlines.items() if dl <= now
                ]
                for k in expired:
                    ind, waiters = self._pending.pop(k)
                    del self._deadlines[k]
                    self._zombies.add(k)
                    self._inflight -= 1
                    self.tel.timeouts += 1
                    self.pool.cache.put(
                        ind, self.penalty_time_s, penalized=True, key=k
                    )
                    self._done.append((ind, self.penalty_time_s))
                    for w in waiters:
                        self._done.append((w, self.penalty_time_s))
                if self._done:
                    break
                nxt = min(self._deadlines.values()) - now
                # idle attribution: lanes with no work while we wait
                starved = max(0, self.pool.workers - self._inflight)
                w0 = time.monotonic()
                self._cond.wait(timeout=max(0.001, min(nxt, 0.5)))
                if starved:
                    self._idle += starved * (time.monotonic() - w0)
            ind, t = self._done.pop(0)
            return ind, float(t)

    def cut(self) -> GenTelemetry:
        """Close the current telemetry window: finalize wall/idle, push
        the row to ``pool.history``, start a fresh window."""
        with self._cond:
            tel = self.tel
            tel.wall_s = time.monotonic() - self._t0
            tel.idle_s = self._idle
            self.tel = GenTelemetry()
            self._t0 = time.monotonic()
            self._idle = 0.0
            self._seen = set()
        self.pool.history.append(tel)
        return tel

    def close(self) -> None:
        if self._ex is not None:
            self._ex.shutdown(wait=False, cancel_futures=True)
            self._ex = None
        # a window the caller never cut still reaches the history
        if self.tel.submitted:
            self.cut()

    def __enter__(self) -> "SteadySession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def parallel_map(
    fn: Callable, items: Sequence, workers: int = 1
) -> List:
    """Order-preserving concurrent map on a thread pool (workers<=1 is a
    plain loop). Shared by benchmark drivers for independent, GIL-releasing
    work such as interpret-mode kernel checks."""
    if workers <= 1 or len(items) <= 1:
        return [fn(x) for x in items]
    with cf.ThreadPoolExecutor(max_workers=workers) as ex:
        return list(ex.map(fn, items))
