"""Evaluation pool: dedup, persistent cache round-trip, timeout penalty,
pool-size GA equivalence, and the pooled wall-clock win."""
import threading
import time

import pytest

from repro.core import evalpool as ep
from repro.core import evaluator as ev
from repro.core import ga, miniapps
from repro.core import transfer as tr


def _onemax_time(genes):
    return 10.0 - 9.0 * sum(genes) / len(genes)


# ---------------------------------------------------------------------------
# dedup + cache accounting
# ---------------------------------------------------------------------------


def test_dedup_within_generation():
    calls = []

    def evaluate(genes):
        calls.append(genes)
        return _onemax_time(genes)

    pool = ep.EvalPool(evaluate)
    pop = [(0, 1), (1, 1), (0, 1), (1, 1), (0, 1)]  # 2 unique of 5
    times, tel = pool.evaluate_generation(pop, 180.0, 1000.0)
    assert len(calls) == 2
    assert tel.submitted == 5 and tel.unique == 2
    assert tel.evaluated == 2 and tel.cache_hits == 3
    assert tel.dedup_ratio == pytest.approx(0.6)
    # results in population order, duplicates identical
    assert times[0] == times[2] == times[4]
    assert times[1] == times[3]


def test_cross_generation_cache_serves_repeats():
    calls = []

    def evaluate(genes):
        calls.append(genes)
        return _onemax_time(genes)

    pool = ep.EvalPool(evaluate)
    pool.evaluate_generation([(0, 0), (1, 1)], 180.0, 1000.0)
    _, tel = pool.evaluate_generation([(0, 0), (1, 0)], 180.0, 1000.0)
    assert len(calls) == 3  # (0,0) served from cache
    assert tel.cache_hits == 1 and tel.evaluated == 1


# ---------------------------------------------------------------------------
# persistent cache: round-trip across a simulated restart
# ---------------------------------------------------------------------------


def test_cache_roundtrip_across_restart(tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    prog = miniapps.himeno_program()
    e = ev.MiniappEvaluator(prog, tr.TransferMode.BULK, staged=True)
    params = ga.GAParams(population=8, generations=4, seed=3)

    cache1 = ep.FitnessCache(path, fingerprint=e.fingerprint())
    with ep.EvalPool(e, cache=cache1) as pool1:
        r1 = ga.run_ga(None, prog.gene_length, params, pool=pool1)
    assert r1.evaluations > 0

    # "restart": new cache object replays the JSONL file
    cache2 = ep.FitnessCache(path, fingerprint=e.fingerprint())
    assert cache2.loaded == r1.evaluations
    with ep.EvalPool(e, cache=cache2) as pool2:
        r2 = ga.run_ga(None, prog.gene_length, params, pool=pool2)
    assert r2.evaluations == 0  # everything served from disk
    assert r2.best_genes == r1.best_genes
    assert r2.best_time_s == r1.best_time_s


def test_cached_hit_revalidated_against_current_timeout(tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    c1 = ep.FitnessCache(path, fingerprint="fp")
    c1.put((0, 1), 500.0)  # measured under a permissive timeout
    c1.close()
    c2 = ep.FitnessCache(path, fingerprint="fp")
    with ep.EvalPool(lambda g: 1.0, cache=c2) as pool:
        times, tel = pool.evaluate_generation(
            [(0, 1)], timeout_s=180.0, penalty_time_s=1000.0
        )
    assert times == [1000.0]  # stale 500s hit scores as penalty now
    assert tel.cache_hits == 1 and tel.evaluated == 0


def test_cache_fingerprint_isolation(tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    c1 = ep.FitnessCache(path, fingerprint="cfg-a")
    c1.put((1, 0, 1), 2.5)
    c1.close()
    # same file, different evaluator configuration: entry must not leak
    c2 = ep.FitnessCache(path, fingerprint="cfg-b")
    assert c2.get((1, 0, 1)) is None
    c3 = ep.FitnessCache(path, fingerprint="cfg-a")
    assert c3.get((1, 0, 1)) == 2.5


def test_penalized_records_not_replayed_on_resume(tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    calls = []

    def flaky(genes):
        calls.append(genes)
        if len(calls) == 1:
            return 500.0  # transient overtime on the very first measurement
        return 1.0

    cache1 = ep.FitnessCache(path, fingerprint="fp")
    with ep.EvalPool(flaky, cache=cache1) as pool:
        times, _ = pool.evaluate_generation(
            [(0,), (1,)], timeout_s=180.0, penalty_time_s=1000.0
        )
    assert times == [1000.0, 1.0]

    # restart: the good measurement is replayed, the penalty is not
    cache2 = ep.FitnessCache(path, fingerprint="fp")
    assert cache2.get((1,)) == 1.0
    assert cache2.get((0,)) is None
    with ep.EvalPool(flaky, cache=cache2) as pool:
        times, tel = pool.evaluate_generation(
            [(0,), (1,)], timeout_s=180.0, penalty_time_s=1000.0
        )
    assert times == [1.0, 1.0]  # re-measured clean this time
    assert tel.cache_hits == 1 and tel.evaluated == 1


def test_pool_close_leaves_caller_cache_open(tmp_path):
    """A caller-owned cache survives its pool: it may be serving other
    pools (cross-subset sharing), so only pool-built caches close with
    the pool."""
    path = str(tmp_path / "fitness.jsonl")
    cache = ep.FitnessCache(path, fingerprint="fp")
    with ep.EvalPool(lambda g: 1.0, cache=cache) as pool:
        pool.evaluate_generation([(0,)], 180.0, 1000.0)
    # still open: a second pool over the same cache keeps persisting
    with ep.EvalPool(lambda g: 2.0, cache=cache) as pool:
        pool.evaluate_generation([(1,)], 180.0, 1000.0)
    cache.close()
    replay = ep.FitnessCache(path, fingerprint="fp")
    assert replay.get((0,)) == 1.0 and replay.get((1,)) == 2.0


def test_cache_tolerates_corrupt_trailing_line(tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    c1 = ep.FitnessCache(path, fingerprint="fp")
    c1.put((0, 1), 1.25)
    c1.put((1, 1), 0.75)
    c1.close()
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"v": 1, "fp": "fp", "genes": "10", "t": 3.')  # killed write
    c2 = ep.FitnessCache(path, fingerprint="fp")
    assert len(c2) == 2
    assert c2.get((0, 1)) == 1.25


# ---------------------------------------------------------------------------
# timeout -> penalty propagation
# ---------------------------------------------------------------------------


def test_overtime_measurement_penalized_in_pool():
    def evaluate(genes):
        return 500.0  # above timeout_s=180

    with ep.EvalPool(evaluate, workers=2) as pool:
        times, tel = pool.evaluate_generation(
            [(0,), (1,)], timeout_s=180.0, penalty_time_s=1000.0
        )
    assert times == [1000.0, 1000.0]
    assert tel.timeouts == 2


def test_hung_measurement_penalized_at_deadline():
    done = threading.Event()

    def evaluate(genes):
        if genes == (1,):
            done.wait(5.0)  # hangs well past the timeout
        return 0.01

    with ep.EvalPool(evaluate, workers=2) as pool:
        t0 = time.monotonic()
        times, tel = pool.evaluate_generation(
            [(0,), (1,)], timeout_s=0.3, penalty_time_s=1000.0
        )
        wall = time.monotonic() - t0
    done.set()
    assert times[0] == 0.01
    assert times[1] == 1000.0
    assert tel.timeouts == 1
    assert wall < 4.0  # scored at the deadline, not at straggler finish


def test_queued_individuals_requeued_not_penalized_after_hang():
    done = threading.Event()

    def evaluate(genes):
        if genes in ((0,), (1,)):
            done.wait(10.0)  # occupies both workers past the deadline
        return 0.01

    # workers=2: (0,) and (1,) hang, so (2,) and (3,) never start before
    # the deadline; they must be re-measured on a fresh executor, not
    # penalized unmeasured
    with ep.EvalPool(evaluate, workers=2) as pool:
        times, tel = pool.evaluate_generation(
            [(0,), (1,), (2,), (3,)], timeout_s=0.2, penalty_time_s=1000.0
        )
    done.set()
    assert times[0] == 1000.0 and times[1] == 1000.0
    assert times[2] == 0.01 and times[3] == 0.01
    assert tel.timeouts == 2


def test_crashing_measurement_penalized():
    def evaluate(genes):
        if sum(genes) == 0:
            raise RuntimeError("compile error analogue")
        return 1.0

    for workers in (1, 3):
        with ep.EvalPool(evaluate, workers=workers) as pool:
            times, tel = pool.evaluate_generation(
                [(0, 0), (1, 0)], timeout_s=180.0, penalty_time_s=1000.0
            )
        assert times == [1000.0, 1.0]


def test_ga_timeout_penalty_through_pool():
    def evaluate(genes):
        return float("inf")

    p = ga.GAParams(population=4, generations=2, seed=0)
    with ep.EvalPool(evaluate, workers=2) as pool:
        r = ga.run_ga(None, 4, p, pool=pool)
    assert r.best_time_s == p.penalty_time_s


# ---------------------------------------------------------------------------
# GA equivalence: same seed => same best individual, pool size 1 vs N
# ---------------------------------------------------------------------------


def test_ga_pool_size_equivalence_miniapp():
    prog = miniapps.himeno_program()
    e = ev.MiniappEvaluator(prog, tr.TransferMode.BULK, staged=True)
    params = ga.GAParams(population=16, generations=10, seed=0)

    serial = ga.run_ga(e, prog.gene_length, params)
    with ep.EvalPool(e, workers=4) as pool:
        pooled = ga.run_ga(None, prog.gene_length, params, pool=pool)

    assert pooled.best_genes == serial.best_genes
    assert pooled.best_time_s == serial.best_time_s
    assert [h.best_time_s for h in pooled.history] == \
        [h.best_time_s for h in serial.history]
    # the pooled cache must do at least as well as the in-memory serial one
    assert pooled.cache_hits >= serial.cache_hits


def test_batched_evaluator_path_used():
    class Batched:
        def __init__(self):
            self.batch_calls = 0
            self.point_calls = 0

        def __call__(self, genes):
            self.point_calls += 1
            return _onemax_time(genes)

        def evaluate_batch(self, genes_list):
            self.batch_calls += 1
            return [_onemax_time(g) for g in genes_list]

    e = Batched()
    with ep.EvalPool(e) as pool:
        times, tel = pool.evaluate_generation(
            [(0, 1), (1, 1), (0, 1)], 180.0, 1000.0
        )
    assert e.batch_calls == 1 and e.point_calls == 0
    assert tel.evaluated == 2


# ---------------------------------------------------------------------------
# measured path: MeasuredEvaluator on real miniapp runs, in this process
# ---------------------------------------------------------------------------


def test_measured_evaluator_through_process_pool():
    """The paper's real measurement loop on a real miniapp run fn: a
    process pool refuses it (a child would find the chip held by this
    process), and the in-line pool measures each canonical placement
    once, in this process."""
    import multiprocessing
    import os

    from repro.offload import programs

    run_fn = programs.measured_run_fn("himeno", "small")
    pids = []

    class InProcess:
        def __call__(self, genes):
            pids.append(os.getpid())
            run_fn(genes)

        def cache_key(self, genes):
            return run_fn.cache_key(genes)

    e = ev.MeasuredEvaluator(InProcess(), tag=run_fn.tag)
    assert "himeno" in ep.evaluator_fingerprint(e)
    with pytest.raises(ValueError, match="process"):
        ep.EvalPool(e, workers=2, executor="process")

    prog = miniapps.himeno_program()
    n = prog.gene_length
    off = (0,) * n
    on = tuple(1 for _ in range(n))
    with ep.EvalPool(e) as pool:
        times, tel = pool.evaluate_generation(
            [off, on, off], timeout_s=300.0, penalty_time_s=1000.0
        )
    assert tel.evaluated == 2 and tel.cache_hits == 1
    assert tel.timeouts == 0
    assert all(0.0 < t < 300.0 for t in times)
    assert times[0] == times[2]
    assert pids == [os.getpid()] * 2
    assert multiprocessing.active_children() == []


def test_run_fns_are_picklable():
    import pickle

    from repro.offload import programs

    for scale in programs.MEASURED_RUN_FNS.values():
        for fn in scale.values():
            clone = pickle.loads(pickle.dumps(
                ev.MeasuredEvaluator(fn, tag=fn.tag)))
            assert clone.tag == fn.tag and clone.run_fn == fn


# ---------------------------------------------------------------------------
# wall-clock: >= 3x per-generation improvement at pool size 4
# ---------------------------------------------------------------------------


def test_pooled_generation_wall_clock_speedup():
    delay = 0.05

    def slow_eval(genes):
        time.sleep(delay)
        return _onemax_time(genes)

    pop = [tuple(int(b) for b in format(i, "04b")) for i in range(12)]

    with ep.EvalPool(slow_eval, workers=1) as pool:
        _, tel1 = pool.evaluate_generation(pop, 180.0, 1000.0)
    with ep.EvalPool(slow_eval, workers=4) as pool:
        _, tel4 = pool.evaluate_generation(pop, 180.0, 1000.0)

    assert tel1.evaluated == tel4.evaluated == 12
    assert tel1.wall_s / tel4.wall_s >= 3.0


# ---------------------------------------------------------------------------
# multi-owner store: the serving layer shares ONE file across pools
# ---------------------------------------------------------------------------


def test_two_caches_two_threads_hammer_one_store(tmp_path):
    """Regression for the multi-owner hazard: two cache objects (as two
    concurrent service jobs would hold) appending to one store must
    never tear a line or lose a record — O_APPEND + flock + one write
    per record."""
    path = str(tmp_path / "fitness.jsonl")
    caches = [ep.FitnessCache(path, fingerprint=f"fp-{i}")
              for i in range(2)]
    n = 200

    def hammer(idx):
        for j in range(n):
            caches[idx].put((idx, j), float(j) + 0.5)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c in caches:
        c.close()
    with open(path, encoding="utf-8") as fh:
        lines = fh.readlines()
    assert len(lines) == 2 * n
    import json as _json

    for line in lines:
        assert line.endswith("\n"), "torn (unterminated) record"
        _json.loads(line)
    for i in range(2):
        replay = ep.FitnessCache(path, fingerprint=f"fp-{i}")
        assert len(replay) == n
        assert replay.get((i, n - 1)) == float(n - 1) + 0.5
        replay.close()


def test_cache_refcount_and_idempotent_close(tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    cache = ep.FitnessCache(path, fingerprint="fp")
    assert cache.retain() is cache
    cache.close()  # releases the retain(); construction ref remains
    cache.put((0,), 1.0)  # descriptor must still be open
    cache.close()
    assert cache._fd is None
    cache.close()  # double-close is a no-op, never an OSError
    cache.close()
    replay = ep.FitnessCache(path, fingerprint="fp")
    assert replay.get((0,)) == 1.0


def test_broker_shares_views_per_fingerprint(tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    with ep.EvalBroker(path) as broker:
        a = broker.open_cache("fp-x")
        b = broker.open_cache("fp-x")
        other = broker.open_cache("fp-y")
        assert a is b and a is not other
        # a measurement one job pays is the sibling's hit IMMEDIATELY —
        # in memory, not only after a file re-read
        a.put((1, 0), 3.25)
        assert b.get((1, 0)) == 3.25
        assert other.get((1, 0)) is None  # fingerprints stay isolated
        assert broker.stats() == {"fp-x": 1, "fp-y": 0}
        # a stage closing "its" cache releases one reference only:
        # the shared view stays usable for the sibling and the broker
        b.close()
        a.put((1, 1), 4.5)
        other.close()
    # broker.close() released ITS references; `a` is still retained by
    # this caller (two open_cache calls, one close so far)
    assert other._fd is None and a._fd is not None
    a.close()
    assert a._fd is None
    replay = ep.FitnessCache(path, fingerprint="fp-x")
    assert len(replay) == 2


def test_broker_view_held_by_stage_survives_broker_close(tmp_path):
    # an in-flight stage's retained view outlives broker.close(): the
    # descriptor closes only when the LAST owner releases
    path = str(tmp_path / "fitness.jsonl")
    broker = ep.EvalBroker(path)
    view = broker.open_cache("fp")
    broker.close()
    view.put((7,), 7.0)  # still open: the stage holds a reference
    view.close()
    assert view._fd is None


# ---------------------------------------------------------------------------
# steady-state sessions: continuous evaluation over shared stores
# ---------------------------------------------------------------------------


class _FpOnemax:
    """onemax with the fingerprint the persistent cache demands."""

    def __call__(self, genes):
        return _onemax_time(genes)

    def fingerprint(self):
        return "steady-onemax"


def test_steady_session_dedup_joins_inflight_measurement():
    calls = []
    started = threading.Event()

    def evaluate(genes):
        calls.append(genes)
        started.set()
        time.sleep(0.05)
        return _onemax_time(genes)

    with ep.EvalPool(evaluate, workers=2) as pool:
        with pool.steady_session(180.0, 1000.0) as ses:
            ses.submit((0, 1))
            started.wait(timeout=5.0)
            ses.submit((0, 1))  # identical genome mid-measurement
            r1 = ses.collect()
            r2 = ses.collect()
            tel = ses.cut()
    assert len(calls) == 1  # the duplicate joined, never re-measured
    assert r1[1] == r2[1] == _onemax_time((0, 1))
    assert tel.submitted == 2 and tel.unique == 1
    assert tel.evaluated == 1 and tel.cache_hits == 1


def test_steady_session_timeout_scores_penalty_once():
    release = threading.Event()

    def evaluate(genes):
        release.wait(timeout=5.0)  # hangs past the session deadline
        return 1.0

    with ep.EvalPool(evaluate, workers=2) as pool:
        with pool.steady_session(0.05, 1000.0) as ses:
            ses.submit((1, 0))
            genes, t = ses.collect()
            assert genes == (1, 0) and t == 1000.0
            release.set()  # the straggler finishes late...
            time.sleep(0.1)
            tel = ses.cut()
    # ...and its late result was discarded: one timeout, no extra
    # result, nothing double-counted
    assert tel.timeouts == 1 and tel.evaluated == 1
    assert tel.submitted == 1


def test_steady_session_collect_without_work_raises():
    with ep.EvalPool(_FpOnemax()) as pool:
        with pool.steady_session(180.0, 1000.0) as ses:
            with pytest.raises(RuntimeError, match="no submission"):
                ses.collect()


def test_steady_sessions_hammer_one_broker_store(tmp_path):
    """Eight steady sessions (eight threads, one shared EvalBroker view)
    hammering one JSONL store: no torn lines, per-session telemetry adds
    up exactly, and the store replays to the distinct key set."""
    path = str(tmp_path / "fitness.jsonl")
    n_threads, n_each = 8, 60
    import random

    with ep.EvalBroker(path) as broker:
        view = broker.open_cache("steady-onemax")
        tels = [None] * n_threads
        errors = []

        def hammer(idx):
            try:
                rng = random.Random(idx)
                with ep.EvalPool(_FpOnemax(), cache=view) as pool:
                    with pool.steady_session(180.0, 1000.0) as ses:
                        for _ in range(n_each):
                            # a small genome space forces cross-session
                            # collisions: simultaneous misses, hits on
                            # another session's fresh measurement
                            ses.submit((rng.randint(0, 1),
                                        rng.randint(0, 1),
                                        rng.randint(0, 1)))
                            ses.collect()
                        tels[idx] = ses.cut()
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)

        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        view.close()
    assert not errors
    # per-session accounting: every submission resolved exactly once
    for tel in tels:
        assert tel is not None
        assert tel.submitted == n_each
        assert tel.evaluated + tel.cache_hits == tel.submitted
        assert tel.timeouts == 0
    total_evaluated = sum(t.evaluated for t in tels)
    import json as _json

    with open(path, encoding="utf-8") as fh:
        lines = fh.readlines()
    # one whole line per fresh measurement — atomic appends, no tearing
    assert len(lines) == total_evaluated
    keys = set()
    for line in lines:
        assert line.endswith("\n"), "torn (unterminated) record"
        keys.add(_json.loads(line)["genes"])
    replay = ep.FitnessCache(path, fingerprint="steady-onemax")
    assert len(replay) == len(keys)
    replay.close()


def test_evaluator_fingerprints_distinguish_configs():
    prog = miniapps.himeno_program()
    a = ev.MiniappEvaluator(prog, tr.TransferMode.BULK, staged=True)
    b = ev.MiniappEvaluator(prog, tr.TransferMode.NEST, staged=False,
                            kernels_only=True)
    assert a.fingerprint() != b.fingerprint()
    assert ep.evaluator_fingerprint(a) == a.fingerprint()
    # a fingerprint-less callable is refused outright: keying the
    # persistent cache on a bare name would let two differently-
    # configured instances share measurements
    with pytest.raises(TypeError, match="fingerprint"):
        ep.evaluator_fingerprint(_onemax_time)
