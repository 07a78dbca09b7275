"""A run whose timed path is broken underneath comes out not correct.

Each case plants one fault in the program under a tiny cell on the CPU and
drives a whole run past the chip check. The faults are those a serving cell
can have: a decode step that hands back its cache unchanged, a token or a
score altered where it is produced, and half of a request's items left
out. (One chip exchanges nothing, so no cell can lose an exchange.)"""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.tiny import (DLRM_CFG, DLRM_MIX, LM_CFG, LM_MIX,
                              checkout)


def stale_cache(mp):
    from repro.serving.engine import InferenceEngine
    build = InferenceEngine._build_decode

    def broken(self):
        step = build(self)

        def fn(params, caches, *rest):
            kept = jax.tree.map(jnp.copy, caches)   # the step donates them
            nxt, _ = step(params, caches, *rest)
            return nxt, kept
        return fn
    mp.setattr(InferenceEngine, "_build_decode", broken)


def wrong_token(mp):
    from repro.models import model
    greedy = model.greedy_next
    mp.setattr(model, "greedy_next",
               lambda params, cfg, h: (greedy(params, cfg, h) + 1)
               % cfg.vocab_size)


def wrong_score(mp):
    from repro.models import dlrm
    dense = dlrm.dense_forward
    mp.setattr(dlrm, "dense_forward",
               lambda *a: dense(*a) * 1.01)


def half_the_items(mp):
    from repro.models import dlrm
    sls = dlrm.sls_forward

    def broken(params, cfg, asn, indices, lengths):
        pooled = sls(params, cfg, asn, indices, lengths)
        keep = jnp.arange(pooled.shape[0]) < pooled.shape[0] // 2
        return pooled * keep[:, None, None]
    mp.setattr(dlrm, "sls_forward", broken)


@pytest.mark.parametrize("cell,fault", [
    ("t-lm", stale_cache),
    ("t-lm", wrong_token),
    ("t-dlrm", wrong_score),
    ("t-dlrm", half_the_items),
])
def test_a_broken_timed_path_is_not_correct(tmp_path, jax_settings, capsys,
                                            monkeypatch, cell, fault):
    root = checkout(tmp_path, [("t-lm", LM_CFG, LM_MIX, 1),
                               ("t-dlrm", DLRM_CFG, DLRM_MIX, 1)])
    fault(monkeypatch)
    code = run.main(["--workload", cell, "--seed", "3", "--seconds", "2"],
                    root=root, platforms=("cpu",))
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert code == 0
    assert res["correct"] is False
    limits = [c for c in res["checks"].values()
              if c["value"] is None or c["value"] > c["limit"]]
    assert limits, res["checks"]
    assert "check " in err.strip().splitlines()[-1]
