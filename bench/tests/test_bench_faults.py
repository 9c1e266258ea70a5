"""A run whose served tokens are altered where they are produced comes out
not correct: in a fused decode block, and in fused admission."""

import pytest

from bench.tests import tiny
from bench.tests.tiny import no_cache  # noqa: F401  (fixture)


@pytest.mark.parametrize("where", ["block", "admit"])
def test_an_altered_token_is_not_correct(where, monkeypatch, no_cache):  # noqa: F811
    from repro.serve.engine import ServingEngine

    vocab = tiny.CONFIG["vocab_size"]
    if where == "block":
        orig = ServingEngine.step_many

        def broken(self, k):
            out = orig(self, k)
            if out:
                rid, tok = out[0]
                out[0] = (rid, (tok + 1) % vocab)
            return out

        monkeypatch.setattr(ServingEngine, "step_many", broken)
    else:
        orig = ServingEngine.admit

        def broken(self, req, slot):
            orig(self, req, slot)
            first = self.outputs[req.rid]
            first[0] = (first[0] + 1) % vocab

        monkeypatch.setattr(ServingEngine, "admit", broken)
    res = tiny.run("qwen1.5-4b.chat.r80", tiny.OPEN)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > tiny.CONFIG["limits"]["widest_gap"]
