"""The readers of the engine's program names and ``serve.*`` spans
(``bench/program_spans.py``): by hand on a made-up trace, and on a tiny LM
cell traced on the CPU."""
import json
import math

import pytest

from bench import counts, run, trace
from bench.tests.tiny import LM_CFG, LM_MIX, checkout

NEW = ("lm.decode_step_hbm_roofline", "lm.prefill_step_mfu",
       "lm.decode_host_gap_ms", "lm.token_readback_ms")

# one layer, d 2, one head of 1, ff 1, vocab 4, 2 B a value: 14 matmul
# parameters a layer (2 x 3 for q, k, v; 1 x 2 for o; 3 x 2 x 1 for the
# MLP) and 8 in the head, so a decode step reads 44 B of weights and 4 B
# of K/V a position; a prefill of 4 tokens is 2 x 14 x 4 + 4 x 10 + 2 x 2
# x 4 = 168 FLOPs
DIMS = counts.Dims(layers=1, d=2, heads=1, kv_heads=1, head_dim=1, ff=1,
                   vocab=4)
PEAKS = {"hbm_bytes_per_s": 1000.0, "bf16_flops_per_s": 1000.0}


def made_up(decode="jit_decode_step", prefill="jit_prefill_step",
            write="jit_slot_write"):
    """Three ticks of 10 s on one device: a prefill tick, then two
    decode-only ticks. The decode ops end at 14 and the next start at 22;
    another program runs 16-17, so the host gap is 2 + 5 = 7 s, of which
    the readback span 13-15 covers 1 s."""
    op = lambda a, b, module: trace.Op(a, b, "op", module, "d0")
    ops = [op(1, 3, prefill), op(3, 4, write), op(5, 7, decode),
           op(11, 14, decode), op(12, 13, decode),      # nested: counts once
           op(16, 17, "jit_convert_element_type"),
           op(22, 25, decode)]
    step = lambda a, b: trace.Span(a, b, "bench.step_once", "main")
    spans = [step(0, 10), step(10, 20), step(20, 30)]
    host = spans + [trace.Span(13, 15, "serve.read", "main"),
                    trace.Span(24, 26, "serve.read", "main")]
    red = trace.Reduced((0, 30), ops, spans, {"main": host}, ["d0"])
    ticks = [{"prefill": True, "prompts": [4], "contexts": [],
              "decode_steps": 1},
             {"prefill": False, "prompts": [], "contexts": [5],
              "decode_steps": 1},
             {"prefill": False, "prompts": [], "contexts": [6],
              "decode_steps": 1}]
    win = {"ticks": ticks, "traced_from": 0}
    return run.RunView({}, {}, {}, PEAKS, {"win": win, "dims": DIMS}, red)


@pytest.mark.parametrize("name,want", [
    # decode-only ticks: 2 x 44 B + (5 + 6) x 4 B over 3 + 3 s x 1000 B/s
    ("lm.decode_step_hbm_roofline", 100 * 132 / 6000),
    # the prefill tick: 168 FLOPs over 2 + 1 s x 1000 FLOP/s
    ("lm.prefill_step_mfu", 100 * 168 / 3000),
    ("lm.decode_host_gap_ms", 7000.0),
    ("lm.token_readback_ms", 1000.0),
])
def test_readers_by_hand(name, want):
    assert run.reader(run.ROOT, name)(made_up()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_names_gives_nothing(name):
    """The parent's programs were all ``jit_fn``: nothing to read, no
    error."""
    view = made_up(decode="jit_fn", prefill="jit_fn", write="jit_fn")
    assert run.reader(run.ROOT, name)(view) is None
    view.trace = None
    assert run.reader(run.ROOT, name)(view) is None


def test_cpu_cell_spans_share_the_device_clock(tmp_path, jax_settings,
                                               capsys):
    root = checkout(tmp_path, [("t-lm", LM_CFG, LM_MIX, 1)])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] += [
        {"name": n, "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "test", "moves": "ttft_p95_ms",
         "workloads": ["t-lm"]} for n in NEW]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert run.main(["--workload", "t-lm", "--seed", str(2 ** 33 + 11),
                     "--seconds", "2", "--trace", "1"], root=root,
                    platforms=("cpu",), keep_trace=True) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    for n in NEW:
        assert math.isfinite(res["metrics"][n]["value"]), n
    assert res["metrics"]["lm.decode_host_gap_ms"]["value"] \
        >= res["metrics"]["lm.token_readback_ms"]["value"] >= 0

    red = trace.reduce(str(root / ".bench_trace" / "t-lm"))
    ticks = red.spans_named("bench.step_once")
    serve = [e for events in red.host.values() for e in events
             if e.name.startswith("serve.")]
    assert {e.name for e in serve} >= {"serve.step", "serve.admit",
                                       "serve.prefill", "serve.decode",
                                       "serve.dispatch", "serve.read"}
    assert sum(e.name == "serve.step" for e in serve) == len(ticks)
    for e in serve:
        assert any(s.start <= e.start and e.end <= s.end for s in ticks), e
    decodes = [e for e in serve if e.name == "serve.decode"]
    ops = [o for o in red.ops if o.module == "jit_decode_step"]
    assert ops and "jit_fn" not in {o.module for o in red.ops}
    for o in ops:
        assert any(d.start <= o.start and o.end <= d.end
                   for d in decodes), o
