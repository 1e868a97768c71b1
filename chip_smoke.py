#!/usr/bin/env python3
"""Chip smoke test: the measured offload path, end to end, on one TPU.

Run from the root of a checkout, on a machine with one TPU chip attached:

    python3 chip_smoke.py [--out DIR]

Everything runs in this one process, the one that holds the chip. Phases,
each printing its own lines:

1. device   — JAX version, ``jax.devices()``, compile-cache directory;
              anything but a TPU fails here (no CPU fallback).
2. himeno   — ``Offloader`` at ``fidelity="measured"`` on Himeno class M
              (128x128x256), a population-4 / 2-generation search: the
              all-host baseline, the winner, the hot loop's placement, the
              penalized measurements and the PCAST verdict.
3. nasft    — the same for NAS FT class A (256x256x128, complex64).
4. kernels  — ``flash_attention`` and ``ssd_scan`` compiled for the chip
              against their ``kernels/ref.py`` oracles, and ``gather_rows``
              at MoE-dispatch width against ``jnp.take``.
5. service  — ``OffloadService`` (what ``python -m repro.offload serve``
              drives): a measured Himeno job, a duplicate of it, and a
              modelled job, drained to DONE with the duplicate coalesced.

The last line of standard output is ``{"ok": true, "device": {...}}``, and
is printed only when every phase passed; any failure exits 1 before it.
Artifacts, traces and fitness caches go under ``--out`` (default
``chiprun_out/chip_smoke`` in the checkout).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
PROGRAMS = ("himeno", "nasft")
# a small GA budget: measurements collapse to the hot-loop gene, so this
# is about two real measurements per program
BUDGET = dict(population=4, generations=2, seed=0)


class SmokeFailure(RuntimeError):
    """A phase's result is wrong (as opposed to a crash inside it)."""


def _say(phase: str, text: str) -> None:
    print(f"[{phase}] {text}", flush=True)


def phase_device() -> dict:
    """JAX's view of the machine; refuses anything but a TPU."""
    import jax

    from repro.core.evaluator import device_info
    from repro.runtime.compile_cache import compile_cache_dir

    info = device_info()
    _say("device", f"jax {jax.__version__}; devices {jax.devices()}; "
                   f"compile cache {compile_cache_dir()}")
    if info["platform"] != "tpu":
        raise SmokeFailure(
            f"JAX found platform {info['platform']!r} "
            f"({info['device_kind']}), not a TPU: this smoke test measures "
            "the chip and never falls back to another device"
        )
    return info


def _jitted_output_devices(program: str, scale: str) -> set:
    """Platforms of the hot loop's jitted output at the measured grid."""
    import numpy as np

    from repro.core import miniapps
    from repro.offload import programs

    fn = programs.measured_run_fn(program, scale)
    if program == "himeno":
        s = miniapps.himeno_init(fn.grid)
        out, _ = miniapps._himeno_sweep_jit()(s.p, s.a, s.b, s.c, s.bnd,
                                              s.wrk1)
    else:
        nx, ny, nz = fn.grid
        u = np.ones((nz, ny, nx), np.complex64)
        k2 = np.zeros((nz, ny, nx), np.float32)
        out = miniapps._nasft_step_jit()(u, k2, np.float32(1.0))
    out.block_until_ready()
    return {d.platform for d in out.devices()}


def phase_measured(program: str, out: str, scale: str = "model") -> dict:
    """One measured-fidelity ``Offloader`` run; returns its summary."""
    from repro.core.evaluator import device_info
    from repro.offload import Offloader, OffloadSpec, programs

    spec = OffloadSpec(program=program, fidelity="measured",
                       measured_scale=scale,
                       cache=os.path.join(out, "fitness.jsonl"), **BUDGET)
    res = Offloader(
        spec, artifact_path=os.path.join(out, f"{program}.offload.json")
    ).run()
    a = res.stage("analyze").payload
    s = res.stage("search").payload
    v = res.stage("verify").payload
    hot = programs.RUNNABLE[program][0]
    pc = v["pcast"]
    d = a["device"]
    _say(program, f"{a['measured_scale']} on {d['platform']} "
                  f"({d['device_kind']}) x{d['count']}")
    _say(program, f"baseline {a['baseline_s']!r} s; best {s['best_time_s']!r}"
                  f" s; hot loop {hot} -> {s['placement'][hot]}")
    _say(program, f"measurements {s['evaluations']} (cache hits "
                  f"{s['cache_hits']}); penalized {s['timeouts']}; PCAST "
                  f"{'PASS' if pc['ok'] else 'FAIL'} max_rel {pc['max_rel']!r}")
    if s["timeouts"]:
        raise SmokeFailure(f"{s['timeouts']} penalized measurement(s)")
    if not pc["ok"]:
        raise SmokeFailure(f"PCAST FAIL:\n{pc['detail']}")
    on = _jitted_output_devices(program, scale)
    want = device_info()["platform"]
    _say(program, f"jitted hot loop output on {sorted(on)}")
    if on != {want}:
        raise SmokeFailure(f"jitted output on {sorted(on)}, not {want}")
    return {"baseline_s": a["baseline_s"], "best_s": s["best_time_s"],
            "placement": s["placement"][hot], "penalized": s["timeouts"],
            "pcast_ok": pc["ok"], "max_rel": pc["max_rel"]}


def _gather_rows_row(scale: str) -> dict:
    """gather_rows_pallas against ``jnp.take``, f32 and packed bf16: a
    copy, so the tolerance is zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.blocks.library import kernel_interpret
    from repro.kernels.gather_rows import gather_rows_pallas

    # moonshot-v1-16b-a3b dispatch: 4096 tokens x d_model 2048, top-6
    N, d, M = (4096, 2048, 4096 * 6) if scale == "model" else (64, 128, 96)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(-1, N, size=M), jnp.int32)  # -1: drop
    interpret = kernel_interpret()
    gather = jax.jit(lambda s, i: gather_rows_pallas(s, i,
                                                     interpret=interpret))
    err = 0.0
    for dtype in (jnp.float32, jnp.bfloat16):
        src = jnp.asarray(rng.standard_normal((N, d)), dtype)
        want = jnp.where(idx[:, None] >= 0,
                         jnp.take(src, jnp.maximum(idx, 0), axis=0), 0)
        got = gather(src, idx)
        err = max(err, float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32)))))
    return {"kernel": "gather_rows", "shape": f"src{(N, d)} f32+bf16",
            "max_abs_err": err, "tol": 0.0, "interpret": interpret,
            "ok": err == 0.0}


def phase_kernels(scale: str = "model") -> list:
    """The library kernels against their oracles; one row per kernel."""
    from repro.blocks import library

    lib = library.default_library()
    rows = [library.oracle_check(lib.get(name))
            for name in ("flash_attention", "ssd_scan")]
    rows.append(_gather_rows_row(scale))
    for r in rows:
        _say("kernels", f"{r['kernel']:15s} {r['shape']:24s} max_abs_err "
                        f"{r['max_abs_err']!r} tol {r['tol']!r} interpret="
                        f"{str(r['interpret']).lower()} "
                        f"{'ok' if r['ok'] else 'FAIL'}")
    bad = [r["kernel"] for r in rows if not r["ok"]]
    if bad:
        raise SmokeFailure(f"kernel error above tolerance: {bad}")
    return rows


def phase_service(out: str, scale: str = "model") -> list:
    """A measured job, its duplicate and a modelled job through the
    service; returns the final job list."""
    from repro.offload import OffloadSpec
    from repro.serve import jobs as jb
    from repro.serve.admission import AdmissionPolicy
    from repro.serve.offload_service import OffloadService

    svc = OffloadService(os.path.join(out, "service"),
                         policy=AdmissionPolicy())
    measured = OffloadSpec(program="himeno", fidelity="measured",
                           measured_scale=scale, **BUDGET)
    modelled = OffloadSpec(program="nasft", **BUDGET)
    first = svc.submit(measured)
    dup = svc.submit(measured)
    other = svc.submit(modelled)
    jobs = svc.run()
    for j in jobs:
        _say("service", f"{j.id} {j.state}"
                        f" coalesced {svc.store.coalesced_count(j.id)}"
                        + (f" error {j.error}" if j.error else ""))
    _say("service", f"duplicate coalesced onto {dup.job_id}: {dup.coalesced}")
    states = {j.id: j.state for j in jobs}
    if sorted(states) != sorted({first.job_id, other.job_id}):
        raise SmokeFailure(f"unexpected jobs {sorted(states)}")
    if any(s != jb.DONE for s in states.values()):
        raise SmokeFailure(f"not every job DONE: {states}")
    if not (dup.coalesced and dup.job_id == first.job_id):
        raise SmokeFailure("the duplicate submission was not coalesced")
    return jobs


def _fresh(out: str) -> None:
    """Drop what an earlier run left in ``out``: its fitness cache would
    turn this run's measurements into cache hits."""
    os.makedirs(out, exist_ok=True)
    for name in ("fitness.jsonl", "service") + tuple(
            f"{p}.offload{ext}" for p in PROGRAMS
            for ext in (".json", ".trace.jsonl")):
        path = os.path.join(out, name)
        if os.path.isdir(path):
            shutil.rmtree(path)
        elif os.path.exists(path):
            os.remove(path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for artifacts, traces and caches")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    phase = "device"
    try:
        t0 = time.perf_counter()
        info = phase_device()
        from repro.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
        _fresh(args.out)
        for phase, run in (
            ("himeno", lambda: phase_measured("himeno", args.out)),
            ("nasft", lambda: phase_measured("nasft", args.out)),
            ("kernels", phase_kernels),
            ("service", lambda: phase_service(args.out)),
        ):
            t = time.perf_counter()
            run()
            _say(phase, f"ok in {time.perf_counter() - t:.1f} s")
    except SmokeFailure as e:
        _say(phase, f"FAIL: {e}")
        return 1
    except Exception as e:  # noqa: BLE001 — every failure ends the run
        traceback.print_exc()
        _say(phase, f"FAIL: {e!r}")
        return 1
    _say("done", f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["device_kind"],
        "count": info["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
