"""Ingest (core/transfer.py via serving/dlrm_engine.py): XLA backend
compiles inside the window per request answered in it. The ingest unpack
compiles programs shaped by each request's contents. Moves
``rank_p95_ms``."""


def read(run):
    done = run.result["e2e"]["completed"]
    return run.win["compiles"] / done if done else None
