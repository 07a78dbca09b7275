"""The LM engine's compiled stages carry stable program names, and its host
loop writes ``serve.*`` spans into the profiler's trace with a fixed
nesting: ``serve.step`` > ``serve.admit`` / ``serve.prefill`` /
``serve.chunk`` / ``serve.decode`` > ``serve.dispatch`` / ``serve.read``.
The benchmark's per-layer metrics read both (``bench/program_spans.py``)."""
import dataclasses
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models import model as M
from repro.serving.engine import InferenceEngine, Request
from repro.serving.executor import StageExecutor
from repro.serving.telemetry import Telemetry

PROGRAMS = {"prefill": "jit_prefill_step", "decode": "jit_decode_step",
            "chunk_prefill": "jit_chunk_prefill_step",
            "slot_write": "jit_slot_write"}
GROUP_SPANS = {"serve.admit", "serve.prefill", "serve.chunk",
               "serve.decode"}
WORK_SPANS = {"serve.prefill", "serve.chunk", "serve.decode"}


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("deepseek-7b"))
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def engine(setup, chunked: bool) -> InferenceEngine:
    cfg, params = setup
    return InferenceEngine(cfg, params, batch_slots=2, max_len=64,
                           prefill_buckets=(16, 32),
                           prefill_chunk=8 if chunked else None)


def requests(cfg, n=3):
    rng = np.random.default_rng(1)
    return [Request(i, rng.integers(0, cfg.vocab_size, 5 + 7 * i)
                    .astype(np.int32), max_new_tokens=3) for i in range(n)]


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["monolithic", "chunked"])
def test_every_stage_lowers_to_its_program_name(setup, monkeypatch,
                                                chunked):
    eng = engine(setup, chunked)
    seen = {}
    dispatch = StageExecutor.dispatch

    def record(self, stage, key, build_fn, *args, **kw):
        fn = self.get(stage, key, build_fn)
        # shapes before the call: the stage may donate its arguments
        seen[stage] = (fn, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
        return dispatch(self, stage, key, build_fn, *args, **kw)

    monkeypatch.setattr(StageExecutor, "dispatch", record)
    eng.run(requests(setup[0]))
    want = {"chunk_prefill", "decode"} if chunked \
        else {"prefill", "slot_write", "decode"}
    assert set(seen) == want
    for stage, (fn, shapes) in seen.items():
        text = fn.lower(*shapes).as_text()
        assert re.search(r"module @(\w+)", text).group(1) == PROGRAMS[stage]


def host_events(trace_dir):
    """serve.* events of the trace, by host thread: (start, end, name,
    stats)."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("serve.")]
            if evs:
                out[(plane.name, line.name)] = sorted(
                    evs, key=lambda e: (e[0], -e[1]))
    return out


def parents(events):
    """Each event with the name of the innermost other serve.* event on
    its thread that holds it (None at the top)."""
    out = []
    for i, (a, b, name, stats) in enumerate(events):
        holders = [e for j, e in enumerate(events) if j != i
                   and e[0] <= a and b <= e[1]
                   and (e[0], -e[1]) < (a, -b)]
        inner = min(holders, key=lambda e: e[1] - e[0]) if holders else None
        out.append((name, inner[2] if inner else None, stats))
    return out


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["monolithic", "chunked"])
def test_spans_nest_step_group_call(setup, tmp_path, chunked):
    eng = engine(setup, chunked)
    eng.run(requests(setup[0], 1))          # compile outside the trace
    reqs = requests(setup[0])
    for r in reqs:
        eng.submit(r)
    steps0 = eng.telemetry.steps
    with jax.profiler.trace(str(tmp_path)):
        while eng.has_work:
            eng.step_once()
    assert all(r.done for r in reqs)
    threads = host_events(tmp_path)
    assert len(threads) == 1                # one thread drives the engine
    tree = parents(next(iter(threads.values())))
    names = {n for n, _, _ in tree}
    work = "serve.chunk" if chunked else "serve.prefill"
    assert names == {"serve.step", "serve.admit", work, "serve.decode",
                     "serve.dispatch", "serve.read"}
    for name, parent, stats in tree:
        if name == "serve.step":
            assert parent is None
        elif name in GROUP_SPANS:
            assert parent == "serve.step", name
        else:
            assert parent in WORK_SPANS, (name, parent)
        if name == work:
            assert stats["bucket"] in (8, 16, 32) and stats["rows"] >= 1
    decodes = sum(n == "serve.decode" for n, _, _ in tree)
    assert decodes == eng.telemetry.steps - steps0
    assert sum(n == "serve.step" for n, _, _ in tree) >= decodes
    # one readback per group and per decode step
    reads = sum(n == "serve.read" for n, _, _ in tree)
    assert reads == sum(n in WORK_SPANS for n, _, _ in tree)


def test_span_arguments_are_not_formatted_without_a_trace():
    """With no trace active, a span's arguments are never turned into
    strings (the engine passes ints; this pins the profiler's behaviour
    that makes the spans free while off)."""
    class Loud:
        def __str__(self):
            raise AssertionError("formatted while no trace is active")

    with jax.profiler.TraceAnnotation("serve.prefill", bucket=Loud()):
        pass


def test_executor_counts_calls_and_keeps_no_enqueue_time():
    assert "stage_dispatch_s" not in {
        f.name for f in dataclasses.fields(Telemetry)}
    tel = Telemetry()
    ex = StageExecutor(tel)
    assert ex.dispatch("add", 1, lambda: (lambda x: x + 1), 1) == 2
    assert tel.stage_calls == {"add": 1}
    tel.reset_serving_stats()
    assert tel.stage_calls == {} and tel.compiles == {"add": 1}
    merged = Telemetry.merged([tel, tel])
    assert merged.compiles == {"add": 2}
