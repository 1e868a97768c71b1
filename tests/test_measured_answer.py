"""A measured offload job hands back its own answer, and takes a caller's
run fn: the winner's placed run that verify's PCAST compared is kept on
the in-memory result, never in the artifact or the trace; a run fn given
to the ``Offloader`` is what the search, the verify re-measure and the
PCAST pair run; and every stage and measured run is a span of the device
trace."""
import collections
import dataclasses
import glob
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.core import miniapps
from repro.offload import Offloader, OffloadResult, OffloadSpec
from repro.offload import programs as op
from repro.offload import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = op.measured_run_fn("himeno", "small")


def _spec(**kw):
    # toy grids: these tests clock the CPU, not the chip
    return OffloadSpec(program="himeno", fidelity="measured",
                       measured_scale="small", population=4, generations=2,
                       **kw)


def _hot_offloaded(res):
    return res.stage("search").payload["placement"]["jacobi_stencil"] \
        != "cpu"


def _reference():
    """The benchmark's plain numpy Himeno (``himenobmtxpa.c``'s sweeps),
    which imports nothing of the program."""
    path = os.path.join(ROOT, "chipbench", "references", "himeno.py")
    spec = importlib.util.spec_from_file_location("himeno_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_result_carries_the_winners_answer():
    res = Offloader(_spec()).run()
    ans = res.answer
    assert set(ans) == {"p", "gosa"}
    # the winner's placed run, as its run fn computes it
    again = SMALL.run(_hot_offloaded(res))
    np.testing.assert_array_equal(ans["p"], again["p"])
    assert ans["gosa"] == again["gosa"]
    # and the published sweeps' answer, within float32 rounding
    ref = _reference().solve(SMALL.grid, SMALL.nn, 0.8)
    np.testing.assert_allclose(ans["p"], ref["p"], rtol=1e-5, atol=1e-6)
    assert float(ans["gosa"]) == pytest.approx(ref["gosa"], rel=1e-4)


def test_the_answer_stays_out_of_the_artifact_and_the_trace(tmp_path):
    path = str(tmp_path / "job.offload.json")
    res = Offloader(_spec(), artifact_path=path).run()
    assert res.answer is not None
    saved = open(path, encoding="utf-8").read()
    # the artifact is what it would be with no answer ...
    assert json.loads(saved) == res.to_dict()
    bare = dataclasses.replace(res, answer=None)
    assert bare == res and bare.to_dict() == res.to_dict()
    bare.save(str(tmp_path / "bare.offload.json"))
    assert open(tmp_path / "bare.offload.json",
                encoding="utf-8").read() == saved
    # ... a loaded one has none to resume from ...
    assert OffloadResult.load(path).answer is None
    # ... and the trace's digest is over records that hold none of it
    trace = trace_mod.load_trace(trace_mod.default_trace_path(path))
    assert trace.digest == res.trace["digest"]
    assert all("answer" not in json.dumps(r) for r in trace.records)


@dataclasses.dataclass(frozen=True)
class Recording(miniapps.HimenoRunFn):
    """A caller's run fn that remembers each run and its answer."""

    calls: list = dataclasses.field(default_factory=list, compare=False,
                                    hash=False)

    def run(self, offloaded):
        out = super().run(offloaded)
        self.calls.append((offloaded, out))
        return out


def test_a_callers_run_fn_reaches_search_verify_and_pcast():
    fn = Recording(grid=(9, 9, 13), nn=2)
    res = Offloader(_spec(repeats=1), run_fn=fn).run()
    search = res.stage("search").payload
    assert "Recording" in search["evaluator"]
    assert ":himeno:9x9x13:nn2@" in search["evaluator"]
    winner = _hot_offloaded(res)
    # analyze's baseline; one run per search measurement; verify's
    # re-measure of the winner; then the PCAST pair, host path first
    runs = [off for off, _ in fn.calls]
    assert len(runs) == 1 + search["evaluations"] + 1 + 2
    assert runs[0] is False
    assert runs[-3:] == [winner, False, winner]
    assert res.answer is fn.calls[-1][1]
    assert res.answer["p"].shape == (9, 9, 13)
    fid = res.stage("verify").payload["fidelity"]
    assert fid["scale"] == "himeno:9x9x13:nn2"


def test_a_run_fn_is_for_measured_fidelity_alone():
    with pytest.raises(ValueError, match="measured"):
        Offloader(OffloadSpec(program="himeno"), run_fn=SMALL).run()


def test_stages_and_measured_runs_are_device_trace_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = Offloader(_spec()).run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    names = collections.Counter(
        e.name for pl in pd.planes if pl.name == "/host:CPU"
        for ln in pl.lines for e in ln.events
        if e.name.startswith("offload."))
    stages = ("calibrate", "analyze", "seed", "search", "verify", "report")
    assert {f"offload.{s}": 1 for s in stages}.items() <= names.items()
    evaluations = res.stage("search").payload["evaluations"]
    # two repeats of the baseline, of each search measurement and of the
    # re-measure; then the PCAST pair
    assert names["offload.measure"] == 2 * (1 + evaluations + 1) + 2
