"""Engine host loop, token readback (the ``serve.read`` spans around each
``np.asarray`` of the sampled tokens): the part of
``lm.decode_host_gap_ms``'s idle gaps that lies inside ``serve.read``
spans, mean per pair of decode-only ticks, in ms: the device waiting while
the step's tokens reach the host. Moves ``itl_p95_ms``."""
from bench.program_spans import decode_gaps, host_spans, overlap


def read(run):
    pairs = decode_gaps(run)
    if not pairs:
        return None
    reads = host_spans(run.trace, "serve.read")
    return 1e3 * sum(overlap(gaps, reads) for gaps in pairs) / len(pairs)
