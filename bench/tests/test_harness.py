"""The harness's whole loop on the CPU at smoke size (the test-only path
that skips the look for a chip), the faults it must catch, and the
measurement entry's refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness, spec
from bench.tests.smoke import smoke_cell

ROOT = str(spec.ROOT)
SEED = 2 ** 31 + 12345


#: the cell's own configuration, and the MoE configuration under its traffic
CONFIGS = ["", "granite-moe-3b-a800m-s75"]


def _run(config="", hook=None, seconds=1.5, control=False):
    cell = smoke_cell("olmo-1b.gen", config=config)
    with open(os.devnull, "w") as log:
        return harness.run(cell, SEED, seconds, False, time.perf_counter(),
                           require_tpu=False, engine_hook=hook, log=log,
                           control=control)


@pytest.mark.parametrize("config", CONFIGS)
def test_cpu_rehearsal(config):
    out = _run(config)
    assert list(out)[-1] == "checks"
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    cell = spec.load_cell("olmo-1b.gen")
    assert set(out["metrics"]) == {m["name"] for m in cell.metrics(False)}
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert set(out["checks"]) == {"logit_gap", "logit_err"}
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("config", CONFIGS)
def test_control_reads_incorrect(config):
    """The float8 reference put in the program's place fails the same
    limits through the harness's own comparison."""
    out = _run(config, control=True)
    assert out["correct"] is False, out["checks"]
    ctl, prog = out["readings"]["control"], out["readings"]["program"]
    assert {k: c["value"] for k, c in out["checks"].items()} == ctl
    assert all(v <= out["checks"][k]["limit"] for k, v in prog.items())


def _alter_token(eng):
    decode = eng._decode

    def broken(tok, pos):
        nxt, logits, cache = decode(tok, pos)
        return (nxt + 1) % eng.cfg.vocab_size, logits, cache
    eng._decode = broken


def _state_unchanged(eng):
    import jax
    import jax.numpy as jnp
    decode = eng._decode

    def broken(tok, pos):
        kept = jax.tree.map(jnp.copy, eng.kv.cache)
        nxt, logits, _ = decode(tok, pos)
        return nxt, logits, kept
    eng._decode = broken


def _half_batch(eng):
    prefill = eng._prefill

    def broken(tokens, pos, lens):
        # every lane writes only the first half of its chunk's tokens
        return prefill(tokens, pos, lens // 2)
    eng._prefill = broken


def _logits_scaled(eng):
    # the served tokens stay the engine's argmax; only the logits move
    decode = eng._decode

    def broken(tok, pos):
        nxt, logits, cache = decode(tok, pos)
        return nxt, logits * 1.25, cache
    eng._decode = broken


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch, _logits_scaled])
@pytest.mark.parametrize("config", CONFIGS)
def test_faults_read_incorrect(config, fault):
    out = _run(config, hook=fault)
    assert out["correct"] is False, out["checks"]


def test_entry_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-1b.gen",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_entry_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for p in bm["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-1b.gen",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
