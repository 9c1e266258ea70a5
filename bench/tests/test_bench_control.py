"""The control: the reference computed in float8, the step below the bf16
the configurations state, fails the comparison that bf16 serving passes."""

from bench.reference import dense_decoder
from bench.tests import tiny
from bench.tests.tiny import no_cache  # noqa: F401  (fixture)


def test_fp8_control_fails_where_bf16_serving_passes(monkeypatch, no_cache):  # noqa: F811
    seen = []
    orig = dense_decoder.gaps

    def with_control(c, seed, items, **kw):
        out = orig(c, seed, items, **dict(kw, control=True))
        seen.append(out)
        return out

    monkeypatch.setattr(dense_decoder, "gaps", with_control)
    res = tiny.run("qwen1.5-4b.chat.r80", tiny.OPEN)
    limit = tiny.CONFIG["limits"]["widest_gap"]
    assert res["correct"], res["checks"]
    (g,) = seen
    served = max(float(x.max()) for x, _ in g)
    control = max(float(y.max()) for _, y in g)
    assert served <= limit < control
