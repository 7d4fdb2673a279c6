"""The DeepSeek-V2-Lite serving cell at test size on the CPU: a run prints
the contract's line with the routing counters, the fp8 control and runs
broken underneath are not correct, and a program without latent attention
or held experts is refused before any weight is made."""
from __future__ import annotations

import functools
import json
import time
from types import SimpleNamespace

import pytest

from bench import harness
from bench.drivers import serve_dsv2
from bench.tests import tiny

CELL = "dsv2lite-serve-1t"


def cell() -> harness.Cell:
    """The committed cell with the tiny configuration (4 of 16 experts
    held, experts 4-7) and the serving cells' shortened 1-tenant traffic."""
    real = harness.resolve_cell(CELL)
    config = json.loads((tiny.DATA / "tiny-dsv2.json").read_text())
    traffic = dict(real.traffic, **tiny.SHRINK["serve-1t"])
    return harness.Cell(real.name, real.chips, config["name"], config,
                        real.traffic_name, traffic, real.end_to_end,
                        real.per_layer)


def run(seconds: float = 1.0, **driver_kw) -> dict:
    driver = SimpleNamespace(run=functools.partial(serve_dsv2.run,
                                                   **driver_kw))
    return harness.run_cell(CELL, 5_000_000_023, seconds, False,
                            require_accel=False, cell=cell(), driver=driver,
                            peaks=tiny.PEAKS)


def test_a_run_prints_the_contract_line(capsys):
    out = run()
    harness.print_result(out)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert set(line["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    c = line["counters"]
    # each decode step routes 4 rows x 2 layers x 6 slots; about a quarter
    # land on the held experts, and no slot is dropped or invented
    assert 0 < c["expert_slots"] <= 4 * 2 * 6 * c["decode_steps"]
    assert 0 < c["experts_hit"] <= 2 * 4 * c["decode_steps"]
    assert c["decode_bytes"] > 0 and c["flops_per_token"] > 0
    read = harness.load_metric_reader("decode_roofline.moe")
    mctx = harness.MetricContext(cell(), c, {}, [], None, tiny.PEAKS,
                                 c["window_s"])
    assert read(mctx) == pytest.approx(
        100 * c["decode_bytes"] / c["window_s"] / 819e9)


def test_the_fp8_control_is_not_correct():
    out = run(control=True)
    assert out["correct"] is False
    c = out["checks"]["logit_gap"]
    assert c["value"] > c["limit"]
    assert out["counters"]["program_gap"] <= c["limit"]


def _token_altered(engine):
    decode = engine._decode

    def bad(p, c, t, pos, routed):
        logits, cache, routed = decode(p, c, t, pos, routed)
        return logits.at[:, :, 5].set(1e4), cache, routed
    engine._decode = bad


def _latent_cache_unchanged(engine):
    decode = engine._decode

    def bad(p, c, t, pos, routed):
        logits, _, routed = decode(p, c, t, pos, routed)
        return logits, c, routed
    engine._decode = bad


def _held_expert_swapped(engine):
    """Experts 4 and 5 (the first two held) trade weights."""
    moe = engine.params["units"]["moe"]
    for k in ("w_gate", "w_up", "w_down"):
        moe[k] = moe[k].at[:, [0, 1]].set(moe[k][:, [1, 0]])


@pytest.mark.parametrize("tamper", [_token_altered, _latent_cache_unchanged,
                                    _held_expert_swapped])
def test_broken_served_tokens_are_not_correct(tamper):
    out = run(tamper=tamper)
    assert out["correct"] is False
    c = out["checks"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_a_program_without_the_model_is_refused_at_once(monkeypatch):
    """On a program whose configuration lacks latent attention, the run
    stops with a clear error before any weight is made."""
    from repro.configs import base
    monkeypatch.delattr(base, "YarnScaling")
    made = []
    monkeypatch.setattr(serve_dsv2, "to_program",
                        lambda *a: made.append(a))
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="no latent attention"):
        run()
    assert not made and time.perf_counter() - t0 < 10
