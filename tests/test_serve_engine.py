"""Continuous-batching engine: scheduler invariants + decode equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.steps import build_serve_step
from repro.models.model import init_cache
from repro.serve import (Request, RequestState, ServeEngine, SlotScheduler,
                         poisson_trace)


def _mk_requests(specs):
    return [Request(rid=i, prompt=[1], max_new_tokens=4, arrival=a)
            for i, a in enumerate(specs)]


# ---------------------------------------------------------- scheduler ------


def test_scheduler_fifo_and_slot_reuse():
    s = SlotScheduler(2)
    reqs = _mk_requests([0.0, 0.0, 1.0, 5.0])
    for r in reqs:
        s.submit(r)
    admitted = s.admit(0.0)
    assert [r.rid for _, r in admitted] == [0, 1]
    assert not s.free
    # nothing free: arrival-due request 2 must wait
    assert s.admit(2.0) == []
    s.release(0)
    # freed slot 0 is reused by the earliest waiting request
    (slot, r2), = s.admit(2.0)
    assert slot == 0 and r2.rid == 2
    # request 3 not due yet
    assert s.admit(2.0) == []
    assert s.has_work


def test_scheduler_no_starvation_under_trace():
    """FIFO by (arrival, rid): admission order equals arrival order even
    when the queue backs up far beyond the slot count."""
    s = SlotScheduler(2)
    r = np.random.default_rng(3)
    arrivals = np.cumsum(r.exponential(0.5, 20))
    reqs = _mk_requests(list(arrivals))
    for req in reqs:
        s.submit(req)
    step = 0
    ttl = {}  # slot -> remaining steps
    while s.has_work and step < 1000:
        for slot, req in s.admit(float(step)):
            ttl[slot] = 3
        for slot in [sl for sl in list(ttl) if sl in s.active]:
            ttl[slot] -= 1
            if ttl[slot] <= 0:
                s.release(slot)
                del ttl[slot]
        step += 1
    assert not s.has_work, "requests starved"
    assert s.admitted_rids == sorted(s.admitted_rids)
    assert len(s.free) == s.num_slots
    assert all(r.state == RequestState.DONE for r in reqs)


# ------------------------------------------------------------- engine ------


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("olmo-1b")
    return ServeEngine(cfg, num_slots=2, max_len=32, sparsity=0.5, seed=0)


def test_lone_request_matches_straight_line_serve(engine):
    """Engine tokens for a single request must equal the old straight-line
    serve() loop (lock-step batch decode, scalar positions) token for
    token — continuous batching changes scheduling, never the math."""
    steps = 10
    req = engine.submit([7], max_new_tokens=steps)
    engine.run()
    assert len(req.tokens) == steps

    cfg = engine.cfg
    step = jax.jit(build_serve_step(cfg), donate_argnums=(1,))
    cache = init_cache(cfg, 1, 32)
    tok = jnp.asarray([[7]], jnp.int32)
    ref = []
    for pos in range(steps):
        nxt, _, cache = step(engine.params, cache, tok, jnp.int32(pos),
                             lm_weight=engine.lm_weight)
        tok = nxt[:, None]
        ref.append(int(nxt[0]))
    assert req.tokens == ref


def test_continuous_batching_drains_and_reuses_slots():
    """More requests than slots under staggered arrivals: every request
    completes its budget, freed slots are recycled mid-flight, and the
    engine drains cleanly.  Fresh engine: arrivals must land relative to
    step 0 for the stagger to be real."""
    engine = ServeEngine(get_smoke_config("olmo-1b"), num_slots=2,
                         max_len=32, sparsity=0.5, seed=0)
    trace = poisson_trace(6, rate=0.7, seed=2,
                          vocab_size=engine.cfg.vocab_size, max_new=(4, 8))
    reqs = [engine.submit(**spec) for spec in trace]
    engine.run()

    assert all(r.state == RequestState.DONE for r in reqs)
    assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
    # 6 requests over 2 slots: at least one slot served multiple requests
    slots = [r.slot for r in reqs]
    assert max(slots.count(s) for s in set(slots)) >= 2
    # mid-flight admission: some admission happened after another request
    # finished but while a third was still decoding (no drain barrier)
    admits = sorted(r.admit_step for r in reqs)
    dones = sorted(r.done_step for r in reqs)
    assert admits[-1] > dones[0]
    # drained: all slots free, queue empty
    assert not engine.scheduler.has_work
    assert len(engine.scheduler.free) == engine.num_slots
    # FIFO admission order
    rids = engine.scheduler.admitted_rids
    assert rids == sorted(rids)


def test_multi_token_prompt_teacher_forcing(engine):
    """Prompt tokens are consumed before generation starts; the generated
    count still honours max_new_tokens exactly."""
    req = engine.submit([3, 5, 7], max_new_tokens=5)
    engine.run()
    assert len(req.tokens) == 5
    assert req.done_step - req.admit_step + 1 == len(req.prompt) - 1 + 5


def test_bitmap_head_is_packed_and_engaged(engine):
    """The LM head is packed once into BitmapWeight and compresses at the
    engine's pruning level (the kernels/ops path runs every step)."""
    assert engine.lm_weight is not None
    assert engine.lm_weight.shape == (engine.cfg.d_model,
                                      engine.cfg.vocab_size)
    assert engine.head_compression > 1.0
    # slot storage is reused (reset, not reallocated) across lifetimes
    engine.submit([2], max_new_tokens=2)
    engine.run()
    assert engine.kv.resets >= 1


# ------------------------------------------------------- serving CLI -------

_CLI_WORKER = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from repro.launch.serve import serve_trace
rep = serve_trace("olmo-1b", smoke=True, requests=3, sparsity=0.5,
                  model_parallel=%MP%, devices=jax.devices()[:%NDEV%],
                  verbose=False)
print(json.dumps({"requests": rep["requests"],
                  "tokens": rep["generated_tokens"]}))
"""


@pytest.mark.parametrize("mp,ndev", [(1, 1), (1, 2), (2, 2)],
                         ids=["mp1-1dev", "mp1-2dev", "mp2-2dev"])
def test_serve_trace_runs_under_the_engine_mesh(mp, ndev):
    """The serving CLI's ``serve_trace`` enters the engine's mesh and
    serves to completion on fake CPU devices, one-device and sharded —
    the path that once raised a ShardingTypeError on every run."""
    import json
    import os
    import subprocess
    import sys
    script = _CLI_WORKER.replace("%MP%", str(mp)).replace("%NDEV%",
                                                         str(ndev))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600,
                         env={**os.environ, "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["requests"] == 3 and res["tokens"] > 0, res
