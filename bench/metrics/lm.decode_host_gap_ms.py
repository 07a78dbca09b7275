"""Engine host loop (``InferenceEngine.step_once`` between two decode
steps): for each pair of consecutive traced ticks that both ran only a
decode step, the device's idle time (gaps in the union of its op
intervals) between the end of one ``jit_decode_step`` and the start of the
next. Mean per pair, in ms. Moves ``itl_p95_ms``."""
from bench.program_spans import decode_gaps


def read(run):
    pairs = decode_gaps(run)
    if not pairs:
        return None
    return 1e3 * sum(b - a for gaps in pairs for a, b in gaps) / len(pairs)
