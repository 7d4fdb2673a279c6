"""The serving cells at test size on the CPU: prompts repeat from the
seed, the benchmark's weights load into the program's layout and agree
with the reference, and a run prints the contract's line."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import harness
from bench.drivers import serve
from bench.reference import qwen_ref
from bench.tests import tiny


def test_program_layout_matches_the_reference():
    """Teacher-forced logits of the program's forward pass, on the
    converted weights, agree with the reference's to bf16 rounding."""
    import jax
    import jax.numpy as jnp
    from repro.models import registry
    cfg = tiny.cell("qwen05b-serve-8t").config
    arch = serve.arch_config(cfg)
    w = qwen_ref.make_weights(cfg, 77)
    params = serve.to_program(cfg, arch, w)
    toks = np.random.default_rng(0).integers(3, 500, (2, 24)).astype(np.int32)
    prog = registry.model_module(arch).forward(arch, params,
                                               jnp.asarray(toks))[0]
    ref = qwen_ref.logits(cfg, w, toks, 0)
    prog = np.asarray(prog[..., :cfg["vocab_size"]], np.float32)
    ref = np.asarray(ref)
    assert np.max(np.abs(prog - ref)) < 0.05 * np.std(ref) * 10
    assert np.mean(prog.argmax(-1) == ref.argmax(-1)) > 0.9
    del jax


def test_prompts_repeat_from_the_seed():
    class Ctx:
        def __init__(self, seed):
            self.seed = seed

        def rng(self, salt):
            return harness.rng(self.seed, salt)

    class Eng:
        def __init__(self):
            self.tenants = {"a": type("T", (), {"group": None,
                                                "queue": []})()}

        def submit(self, name, p):
            self.tenants[name].queue.append(p)

    got = []
    for seed in (2**32 + 5, 2**32 + 5, 2**32 + 6):
        e = Eng()
        serve.Clients(Ctx(seed), ["a"], 512).refill(e, 16, 3)
        got.append(np.stack(e.tenants["a"].queue))
    assert np.array_equal(got[0], got[1])
    assert not np.array_equal(got[0], got[2])


@pytest.mark.parametrize("workload", ["qwen05b-serve-8t",
                                      "qwen05b-serve-1t"])
def test_a_run_prints_the_contract_line(workload, capsys):
    out = tiny.run(workload, seconds=1.0)
    harness.print_result(out)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    c = line["checks"]["logit_gap"]
    assert c["value"] <= c["limit"]
