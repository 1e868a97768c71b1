"""The runnable programs' host spans: each host step of ``himeno_run`` and
``nasft_run`` writes one ``<program>.<step>`` span onto the profiler's
trace, inside the caller's request, and tracing changes nothing the
programs compute."""
import collections
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import miniapps

SPAN = re.compile(r"^(himeno|nasft)\.\w+$")
HIMENO = dict(grid=(9, 9, 17), nn=3)
NASFT = dict(grid=(8, 8, 8), niter=2)


def traced(tmp_path, fn, **kw):
    """``fn(**kw)`` under the profiler, inside a request annotation;
    returns (result, request event, program span events)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("cell request 0"):
            out = fn(**kw)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = [e for pl in ProfileData.from_file(path).planes
              if pl.name == "/host:CPU" for ln in pl.lines for e in ln.events]
    (request,) = [e for e in events if e.name == "cell request 0"]
    return out, request, [e for e in events if SPAN.match(e.name)]


def counts(spans):
    return dict(collections.Counter(e.name for e in spans))


def assert_inside(spans, request):
    for e in spans:
        assert request.start_ns <= e.start_ns, e.name
        assert e.start_ns + e.duration_ns \
            <= request.start_ns + request.duration_ns, e.name


def test_himeno_spans_each_host_step(tmp_path):
    plain = miniapps.himeno_run(**HIMENO)  # also compiles the sweep
    (p, gosa), request, spans = traced(tmp_path, miniapps.himeno_run,
                                       **HIMENO)
    assert counts(spans) == {"himeno.init": 1, "himeno.copy_in": 1,
                             "himeno.sweep": 3, "himeno.gosa_sync": 3,
                             "himeno.copy_out": 1}
    assert_inside(spans, request)
    np.testing.assert_array_equal(p, plain[0])
    assert gosa == plain[1]


def test_nasft_spans_each_host_step(tmp_path):
    plain = miniapps.nasft_run(**NASFT)
    sums, request, spans = traced(tmp_path, miniapps.nasft_run, **NASFT)
    # a draw and a put per float32 plane of u0; k2 is built on the device
    assert counts(spans) == {"nasft.init": 2, "nasft.copy_in": 2,
                             "nasft.fft": 1, "nasft.step": 2,
                             "nasft.checksum": 2, "nasft.copy_out": 1}
    assert_inside(spans, request)
    np.testing.assert_array_equal(sums, plain)


@pytest.mark.parametrize("fn, kw, init", [
    (miniapps.himeno_run, dict(HIMENO, jit_stencil=False), "himeno.init"),
    (miniapps.nasft_run, dict(NASFT, jit_fft=False), "nasft.init"),
])
def test_the_host_path_spans_only_its_init(tmp_path, fn, kw, init):
    plain = fn(**kw)
    out, request, spans = traced(tmp_path, fn, **kw)
    assert counts(spans) == {init: 1}
    assert_inside(spans, request)
    if isinstance(plain, tuple):
        np.testing.assert_array_equal(out[0], plain[0])
        assert out[1] == plain[1]
    else:
        np.testing.assert_array_equal(out, plain)


@pytest.mark.parametrize("fn, kw", [(miniapps.himeno_run, HIMENO),
                                    (miniapps.nasft_run, NASFT)])
def test_spans_never_nest_nor_look_like_a_request(tmp_path, fn, kw):
    """No span wraps another (so none wraps a run), and none ends as the
    harness's request annotations do."""
    _, _, spans = traced(tmp_path, fn, **kw)
    spans = sorted(spans, key=lambda e: e.start_ns)
    for a, b in zip(spans, spans[1:]):
        assert a.start_ns + a.duration_ns <= b.start_ns, (a.name, b.name)
    assert not any(re.search(r" request \d+$", e.name) for e in spans)
