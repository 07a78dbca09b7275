"""Ingest (serving/dlrm_engine.py: DLRMEngine.ingest, core/transfer.py):
host milliseconds inside the engine's ``serve.ingest`` spans (staging,
``device_put``, unpack dispatch) in the traced steps, per request those
steps answered. A program without the span gives nothing. Moves
``completed_rps``."""
from bench.program_spans import host_spans, overlap


def read(run):
    tr = run.trace
    if tr is None or not run.win["traced_steps"]:
        return None
    ingest = host_spans(tr, "serve.ingest")
    spans = sorted(tr.spans_named("bench.step_once"), key=lambda s: s.start)
    steps = [run.win["steps"][i] for i in run.win["traced_steps"]]
    seconds = requests = 0
    for s, st in zip(spans, steps):
        seconds += overlap([(s.start, s.end)], ingest)
        requests += st["requests"]
    if not ingest or not requests:
        return None
    return 1e3 * seconds / requests
