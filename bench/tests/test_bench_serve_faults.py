"""The serving cells' comparison at test size on the CPU: the fp8 control,
and runs whose served tokens are broken underneath, are not correct."""
from __future__ import annotations

import pytest

from bench.tests import tiny


def test_the_fp8_control_is_not_correct():
    """The reference in fp8, judged in the program's place at each position
    of the served tokens, lies above the limit and the run is not correct,
    while the program's own gap on the same tokens lies under it."""
    out = tiny.run("qwen05b-serve-8t", seconds=1.0, control=True)
    assert out["correct"] is False
    c = out["checks"]["logit_gap"]
    assert c["value"] > c["limit"]
    assert out["counters"]["program_gap"] <= c["limit"]


def _token_altered(engine):
    decode = engine._decode

    def bad(p, c, t, pos):
        logits, cache = decode(p, c, t, pos)
        return logits.at[:, :, 5].set(1e4), cache
    engine._decode = bad


def _state_unchanged(engine):
    decode = engine._decode

    def bad(p, c, t, pos):
        logits, _ = decode(p, c, t, pos)
        return logits, c
    engine._decode = bad


def _half_batch(engine):
    decode = engine._decode

    def bad(p, c, t, pos):
        logits, cache = decode(p, c, t, pos)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:1]), cache
    engine._decode = bad


@pytest.mark.parametrize("tamper", [_token_altered, _state_unchanged,
                                    _half_batch])
def test_broken_served_tokens_are_not_correct(tamper):
    out = tiny.run("qwen05b-serve-8t", seconds=1.0, tamper=tamper)
    assert out["correct"] is False
    c = out["checks"]["logit_gap"]
    assert c["value"] > c["limit"]
