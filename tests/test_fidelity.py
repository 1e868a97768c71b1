"""Fidelity subsystem: spec validation, measured evaluator identity,
calibration fit + registry plumbing, calibrated/measured pipelines, and
the modeled-path byte-identity regression (PR-4 parity)."""
import json

import numpy as np
import pytest

from repro.core import evalpool as ep
from repro.core import evaluator as ev
from repro.core import miniapps
from repro.core import transfer as tr
from repro.offload import Offloader, OffloadResult, OffloadSpec, calibrate
from repro.offload import programs as op
from repro.offload.__main__ import main as cli_main


# ---------------------------------------------------------------------------
# spec validation: bad fidelity combinations fail AT SPEC TIME
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw,msg", [
    (dict(program="himeno", fidelity="bogus"), "fidelity"),
    (dict(program="himeno", fidelity="measured", repeats=0), "repeats"),
    # measured: non-runnable programs have nothing to wall-clock
    (dict(program="hetero", fidelity="measured"), "runnable"),
    (dict(program="arch:stablelm-3b", fidelity="measured"), "runnable"),
    # measured: one clock at a time, in the process that holds the chip
    (dict(program="himeno", fidelity="measured", executor="process"),
     "process"),
    (dict(program="himeno", fidelity="measured", workers=2), "process"),
    # measured is a binary-mode feature
    (dict(program="himeno", fidelity="measured", mode="mixed"), "binary"),
    # calibrated: the base registry must exist
    (dict(program="himeno", fidelity="calibrated", hw="no-such-machine"),
     "base registry"),
    (dict(program="arch:stablelm-3b", fidelity="calibrated"), "machine"),
    # the measured scale is one of the table's rows
    (dict(program="himeno", fidelity="measured", measured_scale="huge"),
     "measured_scale"),
])
def test_fidelity_spec_validation(kw, msg):
    with pytest.raises(ValueError, match=msg):
        OffloadSpec(**kw)


def test_fidelity_spec_roundtrip():
    spec = OffloadSpec(program="himeno", fidelity="measured", repeats=3,
                       measured_scale="small")
    assert OffloadSpec.from_json(spec.to_json()) == spec


def test_pr4_era_spec_dict_still_loads():
    """Artifacts written before the fidelity knob existed deserialize to
    fidelity='modeled' (the behavior they were produced under)."""
    d = OffloadSpec(program="himeno").to_dict()
    del d["fidelity"], d["repeats"]
    spec = OffloadSpec.from_dict(d)
    assert spec.fidelity == "modeled"


# ---------------------------------------------------------------------------
# cache-fingerprint invariants (docs/fidelity.md): modeled fingerprints
# are byte-stable, measured ones carry the measurement identity
# ---------------------------------------------------------------------------


def test_modeled_fingerprints_unchanged_by_fidelity_subsystem():
    prog = miniapps.himeno_program()
    e = ev.MiniappEvaluator(prog, tr.TransferMode.BULK, staged=True)
    assert e.fingerprint() == \
        f"miniapp:{prog.fingerprint()}:bulk:staged:quadro-p4000"
    from repro.destinations import MixedEvaluator

    m = MixedEvaluator(prog, ("cpu", "gpu", "fpga"))
    assert m.fingerprint() == \
        f"mixed:{prog.fingerprint()}:{m.registry.fingerprint()}"


def test_measured_fingerprint_carries_host_and_repeats():
    fn = op.measured_run_fn("himeno", "small")
    a = ev.MeasuredEvaluator(fn, repeats=1, tag=fn.tag, host="hostA")
    b = ev.MeasuredEvaluator(fn, repeats=2, tag=fn.tag, host="hostA")
    c = ev.MeasuredEvaluator(fn, repeats=1, tag=fn.tag, host="hostB")
    assert a.fingerprint().startswith("measured:")
    assert len({a.fingerprint(), b.fingerprint(), c.fingerprint()}) == 3
    # default host comes from this machine
    d = ev.MeasuredEvaluator(fn, tag=fn.tag)
    assert d.host and f"@{d.host}" in d.fingerprint()


def test_measured_fingerprint_names_the_device():
    """CPU clocks and chip clocks never share a fitness-cache entry: the
    fingerprint carries the platform and device kind JAX reports."""
    fn = op.measured_run_fn("himeno", "small")
    cpu = ev.MeasuredEvaluator(fn, tag=fn.tag, host="h", device="cpu:cpu")
    tpu = ev.MeasuredEvaluator(fn, tag=fn.tag, host="h",
                               device="tpu:TPU v5 lite")
    assert cpu.fingerprint() != tpu.fingerprint()
    assert cpu.fingerprint().endswith("@h:cpu:cpu")
    # the default is the device this process measures on
    info = ev.device_info()
    here = ev.MeasuredEvaluator(fn, tag=fn.tag, host="h")
    assert here.device == f"{info['platform']}:{info['device_kind']}"
    assert info["count"] >= 1


def test_measured_clocks_take_the_device_one_at_a_time():
    """Concurrent measurements (two service jobs, one chip) run one after
    the other: no two run fns are ever inside the clock together."""
    import threading
    import time

    active, overlaps = [0], []
    guard = threading.Lock()

    def run(genes):
        with guard:
            active[0] += 1
            overlaps.append(active[0])
        time.sleep(0.01)
        with guard:
            active[0] -= 1

    e = ev.MeasuredEvaluator(run, repeats=2, tag="lane", host="h",
                             device="cpu:cpu")
    threads = [threading.Thread(target=e, args=((i,),)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert len(overlaps) == 8 and max(overlaps) == 1


def test_run_fn_cache_key_collapses_to_hot_gene():
    fn = op.measured_run_fn("himeno", "small")
    e = ev.MeasuredEvaluator(fn, tag=fn.tag)
    n = miniapps.himeno_program().gene_length
    hot = op.hot_gene_index("himeno")
    on = [0] * n
    on[hot] = 1
    other = [1] * n
    other[hot] = 0
    assert e.cache_key(on) == "hot=1"
    assert e.cache_key([0] * n) == e.cache_key(other) == "hot=0"
    # MeasuredEvaluator without a canonicalizing run_fn keeps digits
    plain = ev.MeasuredEvaluator(lambda g: None)
    assert plain.cache_key((1, 0, 1)) == "101"


def test_pool_dedups_on_measured_canonical_key():
    calls = []

    class Fn:
        def __call__(self, genes):
            calls.append(tuple(genes))

        def cache_key(self, genes):
            return f"hot={int(bool(genes[0]))}"

    e = ev.MeasuredEvaluator(Fn(), tag="t")
    with ep.EvalPool(e) as pool:
        times, tel = pool.evaluate_generation(
            [(0, 0), (0, 1), (1, 0), (1, 1)], 180.0, 1000.0
        )
    assert tel.evaluated == 2 and tel.cache_hits == 2  # 2 canonical keys
    assert times[0] == times[1] and times[2] == times[3]


def test_process_pool_uses_executor_even_at_one_worker(monkeypatch):
    """executor='process' at workers=1 still goes through the executor:
    the caller asked for child processes. Device measurements never do
    (see test_process_pool_refuses_device_measurements)."""
    seen = {}

    def fake(kind, workers, evaluate, genes_list, timeout_s):
        seen["kind"] = kind
        return [(1.0, False)] * len(genes_list)

    monkeypatch.setattr(ep, "_run_with_executor", fake)
    with ep.EvalPool(lambda g: 99.0, workers=1, executor="process") as pool:
        times, _ = pool.evaluate_generation([(0,)], 180.0, 1000.0)
    assert seen["kind"] == "process" and times == [1.0]
    # thread executor at workers=1 stays inline (pre-pool parity)
    seen.clear()
    with ep.EvalPool(lambda g: 2.0, workers=1, executor="thread") as pool:
        times, _ = pool.evaluate_generation([(0,)], 180.0, 1000.0)
    assert "kind" not in seen and times == [2.0]


# ---------------------------------------------------------------------------
# measured adapter
# ---------------------------------------------------------------------------


def _measured_spec(**kw):
    # toy grids: these tests clock the CPU, not the chip
    return OffloadSpec(program="himeno", fidelity="measured",
                       measured_scale="small", **kw)


def test_measured_adapter_resolution_and_shape():
    ad = op.resolve_adapter(_measured_spec(repeats=2))
    assert isinstance(ad, op.MiniappMeasuredAdapter)
    assert not ad.deterministic
    assert ad.gene_length == 13 and ad.alleles == 2
    e = ad.build_evaluator()
    assert isinstance(e, ev.MeasuredEvaluator) and e.repeats == 2
    model = ad.model_evaluator()
    # the model prediction lives at the MEASURED scale, not paper scale
    assert model.prog.gene_length == ad.gene_length
    assert model.prog.description != ad.prog.description
    pay = ad.analyze_payload()
    assert pay["fidelity"] == "measured" and pay["host"] == e.host
    # the device is read once, when the adapter is built, and recorded
    assert pay["device"] == ad.device == ev.device_info()
    assert e.device == f"{ad.device['platform']}:{ad.device['device_kind']}"
    assert pay["measured_scale"] == "himeno:9x9x17:nn2"
    genes = [0] * 13
    genes[op.hot_gene_index("himeno")] = 1
    assert ad.placement(genes)["jacobi_stencil"] == "gpu"


def test_measured_adapter_baseline_is_a_real_clock():
    t = op.resolve_adapter(_measured_spec()).baseline_time()
    assert 0.0 < t < 60.0  # a wall clock, not an analytic prediction


# ---------------------------------------------------------------------------
# calibration: fit, artifact, registry plumbing
# ---------------------------------------------------------------------------


def _synthetic_measure(rates=(2.0e9, 1.0e11, 5.0e9, 5e-5)):
    cpu, acc, link, launch = rates

    def measure(p, repeats):
        f, b, c = calibrate._region_quantities(calibrate._probe_program(p))
        if p.dest == "host":
            return f / cpu + c * 1e-4
        return f / acc + b / link + c * launch

    return measure


def test_calibration_fit_recovers_synthetic_constants():
    cal = calibrate.run_calibration(name="syn-a",
                                    measure=_synthetic_measure())
    assert cal.constants["cpu_flops"] == pytest.approx(2.0e9, rel=1e-6)
    assert cal.constants["accel_flops_kernels"] == \
        pytest.approx(1.0e11, rel=1e-6)
    assert cal.constants["link_bw"] == pytest.approx(5.0e9, rel=1e-6)
    assert cal.constants["launch_latency"] == pytest.approx(5e-5, rel=1e-6)
    r = cal.residuals()
    assert r["n"] == len(calibrate.DEFAULT_PROBES)
    assert r["max_abs_rel"] < 1e-9  # exact model -> exact fit
    # balance-preserving constants are recorded as pinned, never silent
    assert "cpu_membw" in cal.pinned and "accel_membw" in cal.pinned
    # ratio preservation vs the base machine
    base = ev.QUADRO_P4000
    assert cal.constants["accel_flops_parallel"] / \
        cal.constants["accel_flops_kernels"] == pytest.approx(
            base.accel_flops_parallel / base.accel_flops_kernels)


def test_calibration_digest_tracks_constants():
    a = calibrate.run_calibration(name="syn-b",
                                  measure=_synthetic_measure())
    b = calibrate.run_calibration(name="syn-b",
                                  measure=_synthetic_measure())
    c = calibrate.run_calibration(
        name="syn-b", measure=_synthetic_measure((3.0e9, 1e11, 5e9, 5e-5)))
    assert a.hw_name == b.hw_name  # deterministic
    assert a.hw_name != c.hw_name  # recalibration moves the fingerprint


def test_calibration_save_load_install(tmp_path):
    cal = calibrate.run_calibration(name="syn-install",
                                    measure=_synthetic_measure())
    path = str(tmp_path / "m.calib.json")
    cal.save(path)
    loaded = calibrate.CalibrationResult.load(path)
    assert loaded.to_dict() == cal.to_dict()
    calibrate.install(loaded)
    # binary-mode selection
    hw = op.resolve_hw(OffloadSpec(program="himeno", hw="syn-install"))
    assert hw.name == cal.hw_name
    # mixed-mode selection (validates destinations against the registry)
    spec = OffloadSpec(program="hetero", mode="mixed", hw="syn-install")
    ad = op.resolve_adapter(spec)
    assert ad.machine == "syn-install"
    # installing again without replace fails; with replace succeeds
    with pytest.raises(ValueError, match="already registered"):
        calibrate.install(loaded, replace=False)
    calibrate.install(loaded, replace=True)


def test_builtin_machines_cannot_be_shadowed():
    from repro.destinations import default_registry, register_registry

    with pytest.raises(ValueError, match="built-in"):
        register_registry("quadro-p4000", default_registry, replace=True)
    with pytest.raises(ValueError, match="built-in"):
        op.register_hw_model(ev.QUADRO_P4000, replace=True)


def test_calibrated_registry_preserves_capacities_and_fpga():
    from repro.destinations import calibrated_registry, get_registry

    base = get_registry("p4000-constrained")
    hw = ev.HardwareModel(name="cal-x", cpu_flops=2e9, cpu_membw=4e9,
                          accel_flops_kernels=1e11,
                          accel_flops_parallel=8e10,
                          accel_flops_vector=1e10, accel_membw=5e10,
                          link_bw=5e9, link_latency=1e-5,
                          launch_latency=2e-5)
    reg = calibrated_registry(base, hw, "p4000-constrained-cal")
    gpu = reg.get("gpu")
    from repro.core.loopir import LoopClass

    assert dict(gpu.rates)[LoopClass.TIGHT] == 1e11  # calibrated rate
    assert gpu.memory_bytes == base.get("gpu").memory_bytes  # capacity kept
    assert reg.get("fpga") == base.get("fpga")  # unobservable: untouched
    assert reg.link("cpu", "gpu").bw == 5e9  # calibrated link
    assert reg.link("cpu", "fpga") == base.link("cpu", "fpga")
    assert reg.fingerprint() != base.fingerprint()


def test_probe_set_must_cover_both_destinations():
    with pytest.raises(ValueError, match="host and accel"):
        calibrate.run_calibration(
            probes=[p for p in calibrate.DEFAULT_PROBES
                    if p.dest == "host"],
            measure=_synthetic_measure(),
        )


# ---------------------------------------------------------------------------
# pipelines end to end
# ---------------------------------------------------------------------------


def test_modeled_search_identical_with_fidelity_knob_present():
    """The PR-4 byte-identity regression: an explicit fidelity='modeled'
    spec (and the default) reproduce the pre-fidelity search exactly."""
    a = Offloader(OffloadSpec(program="himeno")).run(until="search")
    b = Offloader(
        OffloadSpec(program="himeno", fidelity="modeled")
    ).run(until="search")
    assert a.best_genes == b.best_genes
    assert a.best_time_s == b.best_time_s
    assert not a.stage("calibrate").payload["applicable"]


def test_calibrated_pipeline_end_to_end(tmp_path, monkeypatch):
    # a trimmed toy-grid probe set keeps the fast tier fast; the default
    # (bigger) set runs in the CLI verb test below
    small = tuple(
        calibrate.Probe(app, grid, steps, dest)
        for app, grid, steps in [
            ("himeno", (9, 9, 17), 2), ("himeno", (9, 9, 17), 4),
            ("nasft", (8, 8, 8), 2), ("nasft", (8, 8, 8), 4),
        ]
        for dest in ("host", "accel")
    )
    monkeypatch.setattr(calibrate, "DEFAULT_PROBES", small)
    path = str(tmp_path / "cal.offload.json")
    spec = OffloadSpec(program="himeno", fidelity="calibrated",
                       measured_scale="small", population=4, generations=3)
    res = Offloader(spec, artifact_path=path).run()
    c = res.stage("calibrate").payload
    assert c["applicable"] and c["entry"] == "quadro-p4000-calibrated"
    assert c["residuals"]["n"] == 8
    assert c["calibration"]["constants"]["cpu_flops"] > 0
    # the search priced candidates under the calibrated machine: its
    # fingerprint carries the constants digest, never the modeled name
    fp = res.stage("search").payload["evaluator"]
    assert c["hw_name"].split("-")[-1] in fp
    assert fp != Offloader(OffloadSpec(program="himeno")).run(
        until="search").stage("search").payload["evaluator"]
    # predicted-vs-measured section, one row per destination involved
    fid = res.stage("verify").payload["fidelity"]
    assert fid["level"] == "calibrated"
    assert [r["placement"] for r in fid["rows"]] == \
        ["all-host", "winner:hot-loop"]
    assert all(r["ratio"] > 0 for r in fid["rows"])
    assert "fidelity[calibrated" in res.stage("report").payload["text"]

    # resume in a "new process": the calibration is rebuilt from the
    # artifact payload (same digest), not re-measured
    off2 = Offloader.resume(path)
    assert off2.adapter.hw.name == c["hw_name"]
    assert OffloadResult.load(path).calibration is not None


def test_injected_calibration_skips_the_probe_sweep(monkeypatch):
    """Offloader(calibration=...) records the provided fit instead of
    re-measuring (calibrate once, search many apps)."""
    cal = calibrate.run_calibration(measure=_synthetic_measure())

    def boom(*a, **kw):
        raise AssertionError("probe sweep must not run")

    monkeypatch.setattr(calibrate, "run_calibration", boom)
    spec = OffloadSpec(program="himeno", fidelity="calibrated",
                       population=4, generations=2)
    res = Offloader(spec, calibration=cal).run(until="search")
    c = res.stage("calibrate").payload
    assert c["provided"] and c["hw_name"] == cal.hw_name
    assert cal.digest in res.stage("search").payload["evaluator"]
    # a calibration fitted for another base is rejected up front
    with pytest.raises(ValueError, match="base"):
        Offloader(OffloadSpec(program="himeno", fidelity="calibrated",
                              hw="tpu-v5e-host"), calibration=cal)


def test_calibrated_mixed_spec_resolves_after_install():
    cal = calibrate.run_calibration(name="syn-mixed",
                                    measure=_synthetic_measure())
    calibrate.install(cal)
    res = Offloader(
        OffloadSpec(program="hetero", mode="mixed", hw="syn-mixed",
                    population=6, generations=3)
    ).run(until="search")
    assert res.best_time_s > 0
    assert "syn-mixed" in res.stage("search").payload["evaluator"]


def test_measured_verify_refuses_foreign_host_artifact(tmp_path):
    """A measured artifact resumed on a different host must not bless
    the winner: the measurement fingerprint (host-bound) mismatches."""
    spec = _measured_spec(population=2, generations=1,
                          cache=str(tmp_path / "f.jsonl"))
    off = Offloader(spec, artifact_path=str(tmp_path / "a.json"))
    # fake the search record of a run measured elsewhere
    off.run(until="seed")
    e = off.adapter.build_evaluator()
    foreign = e.fingerprint().replace(f"@{e.host}", "@elsewhere")
    off.result.record("search", {
        "best_genes": [0] * 13, "best_time_s": 0.5, "evaluator": foreign,
    }, 0.0)
    from repro.offload import StageFailure

    with pytest.raises(StageFailure, match="differs"):
        off.run_stage("verify")


@pytest.mark.slow
def test_measured_fidelity_smoke_through_subprocesses(tmp_path):
    """Nightly smoke: the whole measured-fidelity pipeline — himeno, tiny
    budget — prices the winner with real wall clocks taken in THIS
    process (the one that holds the device), never in a child."""
    import multiprocessing

    spec = _measured_spec(repeats=2, population=4, generations=2,
                          cache=str(tmp_path / "fitness.jsonl"))
    res = Offloader(spec,
                    artifact_path=str(tmp_path / "m.offload.json")).run()
    assert multiprocessing.active_children() == []
    p = res.stage("search").payload
    assert p["evaluator"].startswith("measured:")
    info = ev.device_info()
    assert p["evaluator"].endswith(
        f":{info['platform']}:{info['device_kind']}")
    assert p["evaluations"] >= 1  # >=1 real in-process measurement
    assert p["timeouts"] == 0
    assert p["best_time_s"] > 0
    a = res.stage("analyze").payload
    assert a["baseline_s"] > 0 and a["device"] == info
    assert res.stage("verify").payload["pcast"]["ok"]
    assert f"device: {info['platform']}" in \
        res.stage("report").payload["text"]
    fid = res.stage("verify").payload["fidelity"]
    assert fid["level"] == "measured" and len(fid["rows"]) == 2
    assert "fidelity[measured" in res.stage("report").payload["text"]
    # the persistent cache is shared with the report stage's modeled
    # stability re-runs, but fingerprints keep the levels isolated:
    # every measurement sits under the measured fingerprint, and no
    # modeled entry can ever masquerade as one
    recs = [json.loads(l) for l in
            open(tmp_path / "fitness.jsonl", encoding="utf-8")]
    measured = [r for r in recs if r["fp"].startswith("measured:")]
    assert measured and len(measured) == p["evaluations"]
    assert all(r["genes"].startswith("hot=") for r in measured)
    assert all("measured" not in r["fp"]
               for r in recs if r not in measured)


def test_measured_crash_fails_the_run(tmp_path, monkeypatch):
    """A measurement that raises fails the search loudly instead of being
    scored as the penalty (which would hand the win to the all-host
    placement and exit 0)."""
    real = miniapps.himeno_run

    def crashing(grid, nn, jit_stencil=True):
        if jit_stencil:
            raise RuntimeError("device lost mid-measurement")
        return real(grid, nn, jit_stencil=False)

    monkeypatch.setattr(miniapps, "himeno_run", crashing)
    spec = _measured_spec(population=4, generations=2)
    off = Offloader(spec, artifact_path=str(tmp_path / "c.json"))
    with pytest.raises(RuntimeError, match="device lost"):
        off.run()
    assert off.result.stages["search"].status == "failed"
    assert not off.result.completed("search")


def test_process_pool_refuses_device_measurements():
    """A MeasuredEvaluator never runs in a child process, nor beside
    another clock: the pool refuses both, and measures in-line."""
    import os

    pids = []

    def run(genes):
        pids.append(os.getpid())

    e = ev.MeasuredEvaluator(run, tag="pid", host="h", device="cpu:cpu")
    for kw in (dict(executor="process"), dict(workers=2)):
        with pytest.raises(ValueError, match="holds the chip"):
            ep.EvalPool(e, **kw)
    with ep.EvalPool(e) as pool:
        pool.evaluate_generation([(0,), (1,)], 180.0, 1000.0)
    assert pids == [os.getpid()] * 2


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_calibrate_verb_and_calibrated_run(tmp_path, capsys):
    out = str(tmp_path / "p4000.calib.json")
    rc = cli_main(["calibrate", "--base", "quadro-p4000",
                   "--name", "cli-cal", "--repeats", "2", "--out", out])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "residuals" in printed and "cli-cal" in printed
    cal = calibrate.CalibrationResult.load(out)
    assert cal.name == "cli-cal" and cal.residuals()["n"] == 16

    # a later invocation installs the file and selects the entry by name
    art = str(tmp_path / "cli.offload.json")
    rc = cli_main(["run", "--program", "himeno", "--hw", "cli-cal",
                   "--calibration", out, "--population", "4",
                   "--generations", "2", "--quiet", "--until", "search",
                   "--artifact", art])
    assert rc == 0
    assert cal.hw_name.split("-")[-1] in \
        OffloadResult.load(art).stage("search").payload["evaluator"]
