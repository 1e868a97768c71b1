"""Function-block offloading (src/repro/blocks/, docs/blocks.md):
library + matcher + substitution evaluator units, the blocks-off parity
contract, the calibration hook, and the ISSUE-8 acceptance surface —
the blocks-on search strictly beating the loop-level best with the
winner's substitutions oracle-checked in verify and visible in report
and trace.
"""
import json

import pytest

from repro.blocks import (
    BlockMixedEvaluator,
    default_library,
    fused_loop,
    internal_vars,
    match_blocks,
    register_kernel_gains,
    substituted_program,
)
from repro.blocks.library import KernelLibrary
from repro.core import miniapps
from repro.destinations.mixed import MixedEvaluator
from repro.offload import Offloader, OffloadSpec
from repro.offload import trace as tr
from repro.offload.programs import MiniappMixedAdapter, resolve_adapter

HETERO = miniapps.hetero_program()
LIB = default_library()

# the two hetero chains the default library matches (asserted exactly:
# the matcher is deterministic and these names anchor docs/blocks.md)
FLASH_CHAIN = ("load_frame", "stencil_a", "stencil_b")
SSD_CHAIN = ("scan_stage1", "scan_stage2", "scan_stage3", "scan_stage4")


# ---------------------------------------------------------------------------
# library
# ---------------------------------------------------------------------------


def test_library_lookup_and_fingerprint():
    assert LIB.get("flash_attention").impl == "flash_attention"
    with pytest.raises(KeyError):
        LIB.get("nope")
    assert LIB.fingerprint().startswith("kernlib-")
    # gains are priced, so they must move the fingerprint
    register_kernel_gains("test-hw-x", {"flash_attention": 2.0})
    assert default_library(hw="test-hw-x").fingerprint() != LIB.fingerprint()
    assert default_library(hw="test-hw-x").get("flash_attention").gain == 2.0
    # unknown hw: stock gains
    assert default_library(hw="no-such-hw").fingerprint() == LIB.fingerprint()


def test_library_rejects_duplicates_and_bad_gain():
    e = LIB.get("flash_attention")
    with pytest.raises(AssertionError):
        KernelLibrary((e, e))
    import dataclasses
    with pytest.raises(AssertionError):
        dataclasses.replace(e, gain=0.0)


# ---------------------------------------------------------------------------
# matching the real miniapps
# ---------------------------------------------------------------------------


def test_match_hetero_exact():
    matches = match_blocks(HETERO, LIB)
    assert [(m.entry, m.loops) for m in matches] == [
        ("flash_attention", FLASH_CHAIN),
        ("ssd_scan", SSD_CHAIN),
    ]
    assert all(m.parent_seq == "frame_iter" for m in matches)


def test_match_other_miniapps():
    # the matcher generalizes beyond the program it was designed around:
    # himeno's stencil+copy pair and nasft's per-dimension fft chains
    # are library-shaped too
    himeno = match_blocks(miniapps.himeno_program(), LIB)
    assert [(m.entry, m.loops) for m in himeno] == [
        ("flash_attention", ("jacobi_stencil", "jacobi_copy")),
    ]
    nasft = match_blocks(miniapps.nasft_program(), LIB)
    assert len(nasft) == 4
    assert all(m.entry == "flash_attention" for m in nasft)


# ---------------------------------------------------------------------------
# substitution: fused nest + variant program
# ---------------------------------------------------------------------------


def test_internal_vars_and_fused_loop():
    flash = match_blocks(HETERO, LIB)[0]
    entry = LIB.get(flash.entry)
    by_name = {l.name: l for l in HETERO.loops}
    chain = [by_name[n] for n in flash.loops]
    internal = internal_vars(HETERO, flash)
    chain_writes = frozenset().union(*(l.writes for l in chain))
    assert internal <= chain_writes
    # internal means exactly: no loop outside the chain touches it
    outside = [l for l in HETERO.loops if l.name not in flash.loops]
    for v in internal:
        assert not any(v in l.touched() for l in outside)
    fused = fused_loop(HETERO, flash, entry)
    assert fused.name == "block:flash_attention:load_frame"
    assert not fused.sequential_carry and fused.trip == 1
    assert fused.parent_seq == chain[0].parent_seq
    assert fused.total_flops == pytest.approx(
        sum(l.total_flops for l in chain) / entry.gain
    )
    assert not (fused.reads & internal) and not (fused.writes & internal)


def test_substituted_program_collapses_chain():
    flash = match_blocks(HETERO, LIB)[0]
    sub = substituted_program(HETERO, [(flash, LIB.get(flash.entry))])
    assert len(sub.loops) == len(HETERO.loops) - len(flash.loops) + 1
    names = [l.name for l in sub.loops]
    assert "block:flash_attention:load_frame" in names
    assert not (set(flash.loops) & set(names))
    # a different program must never share fitness-cache identity
    assert sub.fingerprint() != HETERO.fingerprint()


# ---------------------------------------------------------------------------
# the evaluator: genome semantics, pricing, cache identity
# ---------------------------------------------------------------------------


def _ev() -> BlockMixedEvaluator:
    return BlockMixedEvaluator(HETERO)  # cpu, gpu, fpga


def test_genome_layout_and_eligibility_clamp():
    e = _ev()
    assert e.gene_length == HETERO.gene_length + 2
    assert e.k == 3
    loops = (0,) * HETERO.gene_length
    # flash_attention lists gpu/tpu kinds only: the fpga allele (2)
    # clamps to 0; ssd_scan lists fpga too, so it keeps its allele
    assert e.admissible(loops + (2, 2))[-2:] == (0, 2)
    assert e.admissible(loops + (1, 1))[-2:] == (1, 1)


def test_inactive_blocks_price_exactly_like_the_base_evaluator():
    e = _ev()
    base = MixedEvaluator(HETERO)
    for genes in ((0,) * 12, (1,) * 12, (1, 0, 1, 2, 1, 2, 2, 2, 2, 2, 2, 0)):
        assert e(genes + (0, 0)) == base(genes)
    assert e.fingerprint() != base.fingerprint()
    assert e.fingerprint().startswith("blocks:")
    assert e.host_only_time() == base.host_only_time()


def test_substitution_strictly_beats_loop_level_pricing():
    e = _ev()
    all_gpu = (1,) * 12
    assert e(all_gpu + (1, 1)) < e(all_gpu + (0, 0))


def test_placement_and_substitution_rows():
    e = _ev()
    genes = (1,) * 12 + (1, 1)
    place = e.placement(genes)
    for name in FLASH_CHAIN + SSD_CHAIN:
        assert place[name] == "gpu"
    rows = e.substitutions(genes)
    assert [(r["entry"], r["active"], r["destination"]) for r in rows] == [
        ("flash_attention", True, "gpu"), ("ssd_scan", True, "gpu"),
    ]
    rows0 = e.substitutions((1,) * 12 + (0, 0))
    assert all(not r["active"] and r["destination"] is None for r in rows0)


def test_cache_keys_cover_block_decisions_and_ignore_dead_genes():
    e = _ev()
    loops = (0,) * 12
    k_off = e.cache_key(loops + (0, 0))
    k_on = e.cache_key(loops + (1, 1))
    assert k_off != k_on and "|blocks=" in k_off
    # genomes differing only in a covered loop's (dead) gene share a key
    head, dead = list(loops), list(loops)
    dead[2] = 2  # load_frame: covered by the active flash block
    assert e.cache_key(tuple(head) + (1, 1)) == \
        e.cache_key(tuple(dead) + (1, 1))
    # ...but NOT when the block is inactive (the gene is live again)
    assert e.cache_key(tuple(head) + (0, 1)) != \
        e.cache_key(tuple(dead) + (0, 1))


# ---------------------------------------------------------------------------
# spec + adapter: the blocks-off parity contract
# ---------------------------------------------------------------------------


def test_spec_blocks_is_mixed_only_and_serializes_sparsely():
    with pytest.raises(ValueError, match="mixed"):
        OffloadSpec(program="himeno", mode="binary", blocks=True)
    off = OffloadSpec(program="hetero", mode="mixed")
    assert not off.blocks
    # unset => serialised as False, and round-trips
    assert off.to_dict()["blocks"] is False
    assert OffloadSpec.from_dict(off.to_dict()) == off
    assert OffloadSpec.from_json(off.to_json()) == off
    on = OffloadSpec(program="hetero", mode="mixed", blocks=True)
    assert on.to_dict()["blocks"] is True
    assert OffloadSpec.from_dict(on.to_dict()) == on


def test_adapter_parity_when_blocks_off():
    spec = OffloadSpec(program="hetero", mode="mixed")
    adapter = resolve_adapter(spec)
    ev = adapter.build_evaluator()
    assert isinstance(ev, MixedEvaluator)
    assert not ev.fingerprint().startswith("blocks:")
    assert adapter.gene_length == HETERO.gene_length
    assert "blocks" not in adapter.analyze_payload()
    assert adapter.substitutions((0,) * adapter.gene_length) is None


def test_adapter_blocks_on_wires_the_evaluator():
    spec = OffloadSpec(program="hetero", mode="mixed", blocks=True)
    adapter = resolve_adapter(spec)
    assert isinstance(adapter.build_evaluator(), BlockMixedEvaluator)
    assert adapter.gene_length == HETERO.gene_length + 2
    payload = adapter.analyze_payload()
    assert [m["entry"] for m in payload["blocks"]["matches"]] == [
        "flash_attention", "ssd_scan"
    ]
    # the warm-start sub-searches carry the block genes too
    sub = adapter.sub_evaluator(("cpu", "gpu"))
    assert isinstance(sub, BlockMixedEvaluator)
    assert sub.gene_length == adapter.gene_length


def test_adapter_zero_matches_falls_back_to_plain_evaluator(monkeypatch):
    # a program without library-shaped chains must search byte-
    # identically to a blocks-off run even when the flag is set
    monkeypatch.setattr("repro.blocks.match_blocks", lambda p, lib: ())
    spec = OffloadSpec(program="hetero", mode="mixed", blocks=True)
    adapter = MiniappMixedAdapter(spec, None)
    ev = adapter.build_evaluator()
    assert isinstance(ev, MixedEvaluator)
    assert ev.fingerprint() == MixedEvaluator(HETERO).fingerprint()
    assert adapter.analyze_payload()["blocks"]["matches"] == []


# ---------------------------------------------------------------------------
# calibration: fitted per-kernel gains
# ---------------------------------------------------------------------------


def _fake_probe_measure(p, repeats):
    from repro.offload.calibrate import _probe_program, _region_quantities

    f, b, c = _region_quantities(_probe_program(p))
    return f / 1e9 + (b / 5e9 + c * 1e-4 if p.dest == "accel" else 0.0)


def test_calibration_fits_and_installs_kernel_gains():
    from repro.offload import calibrate as cal_mod

    kw = dict(base="quadro-p4000", repeats=1, name="blocks-test-cal",
              measure=_fake_probe_measure)
    plain = cal_mod.run_calibration(**kw)
    assert plain.kernel_constants == {}
    assert "kernel_constants" not in plain.to_dict()  # old files unchanged

    cal = cal_mod.run_calibration(
        **kw, kernels=True, kernel_measure=lambda entry: (3.0, 1.0)
    )
    assert cal.kernel_constants == {"flash_attention": 3.0, "ssd_scan": 3.0}
    # kernel gains are priced, so they must shift the cache identity
    assert cal.digest != plain.digest
    rt = cal_mod.CalibrationResult.from_dict(
        json.loads(json.dumps(cal.to_dict()))
    )
    assert rt.kernel_constants == cal.kernel_constants
    assert rt.digest == cal.digest

    cal_mod.install(cal)
    lib = default_library(hw=cal.name)
    assert {e.name: e.gain for e in lib.entries} == cal.kernel_constants
    assert lib.fingerprint() != LIB.fingerprint()


# ---------------------------------------------------------------------------
# acceptance: the pipeline end to end
# ---------------------------------------------------------------------------


def _smoke_spec(blocks: bool) -> OffloadSpec:
    return OffloadSpec(program="hetero", mode="mixed", blocks=blocks,
                       population=10, generations=8, warm_start=True)


def test_blocks_search_strictly_beats_loop_level_search():
    res_off = Offloader(_smoke_spec(False)).run(until="search")
    res_on = Offloader(_smoke_spec(True)).run(until="search")
    assert res_on.best_time_s < res_off.best_time_s
    subs = res_on.stage("search").payload["substitutions"]
    assert any(s["active"] for s in subs)
    # blocks-off searches must not even carry the key
    assert "substitutions" not in res_off.stage("search").payload


def test_full_pipeline_verifies_reports_and_traces_substitutions(tmp_path):
    art = str(tmp_path / "blocks.offload.json")
    res = Offloader(_smoke_spec(True), artifact_path=art).run()

    oracles = res.stage("verify").payload["block_oracles"]
    assert oracles and all(r["ok"] for r in oracles)
    assert {r["kernel"] for r in oracles} <= {"flash_attention", "ssd_scan"}
    assert all(r["max_abs_err"] <= r["tol"] for r in oracles)

    text = res.stage("report").payload["text"]
    assert "blocks substituted" in text and "[ssd_scan]" in text
    assert "block oracles:" in text and "PASS" in text

    trace = tr.load_trace(tr.default_trace_path(art))
    match_events = [e for e in trace.events("analyze")
                    if e["name"] == "block_match"]
    sub_events = [e for e in trace.events("verify")
                  if e["name"] == "block_substitution"]
    assert len(match_events) == 2
    assert sub_events and all(e["attrs"]["oracle_ok"] for e in sub_events)
    rendered = tr.render_trace(trace, res)
    assert "block [" in rendered and "oracle PASS" in rendered


def test_blocks_off_pipeline_has_no_block_artifacts(tmp_path):
    art = str(tmp_path / "plain.offload.json")
    res = Offloader(_smoke_spec(False), artifact_path=art).run()
    assert "block_oracles" not in res.stage("verify").payload
    assert "blocks" not in res.stage("analyze").payload
    assert "block" not in res.stage("report").payload["text"]
    trace = tr.load_trace(tr.default_trace_path(art))
    assert not [e for e in trace.events()
                if e["name"].startswith("block_")]
