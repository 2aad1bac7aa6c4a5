"""Sharded packed serving over a real (fake-device) mesh.

Each scenario runs the full engine twice in subprocesses — once on a
single device (the oracle) and once on an 8-fake-device host mesh
(``--xla_force_host_platform_device_count=8``) at one or more
``model_parallel`` settings — and asserts:

* **token bit-identity**: every request's sampled tokens match the
  single-device oracle exactly (greedy and temperature-sampled alike —
  the sampled requests are what make the comparison discriminating);
* **zero unexpected fallbacks**: the ``report()["fallbacks"]`` key set
  matches the oracle's (granite's vocab=255 head falls back to dense on
  *every* topology), no reason mentions ``model_parallel`` (the old
  mp>1 stream/paging fallbacks are retired), and mp>1 runs shard every
  eligible tensor (empty ``shard_fallbacks``);
* **per-device weight HBM ~ 1/mp**: summed over the sharded manifest
  entries, device bytes are the total floor-divided per tensor.

Sharding that cannot apply (indivisible dims, vocab the shard count
does not divide) must degrade to a typed per-tensor reason — never a
crash — which the non-subprocess tests at the bottom pin directly.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

TIMEOUT = 600

_WORKER = textwrap.dedent("""
    import json, os, warnings
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%NDEV%")
    from repro.configs import get_smoke_config
    from repro.serve.engine import ServeEngine

    SPEC = json.loads('''%SPEC%''')


    def run(mp):
        cfg = get_smoke_config(SPEC["arch"])
        kw = {}
        if SPEC["paged"]:
            kw.update(paged=True, page_len=8, prefix_reuse=True,
                      preempt=True)
        if SPEC["prefill_chunk"]:
            kw["prefill_chunk"] = SPEC["prefill_chunk"]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # granite head fallback
            eng = ServeEngine(cfg, num_slots=SPEC["num_slots"],
                              max_len=48, sparsity=SPEC["sparsity"],
                              model_parallel=mp, seed=0, **kw)
        prompts = [[1 + (i * 7 + j) % 250 for j in range(5 + i % 4)]
                   for i in range(6)]
        for i, p in enumerate(prompts):
            eng.submit(p, max_new_tokens=6, arrival=float(i // 2),
                       temperature=(0.8 if i % 2 else 0.0),
                       seed=100 + i, top_k=(8 if i % 2 else None))
        rep = eng.run()
        ws = rep["weight_stream"]
        if SPEC["paged"]:
            eng.kv.audit()
        sh_dev = sh_tot = nsh = 0
        if eng.packed is not None:
            for e in eng.packed.manifest:
                if e.shard is not None:
                    nsh += 1
                    sh_tot += int(e.sparse_bytes)
                    sh_dev += int(e.sparse_bytes) // e.shard[1]
        return {
            "mesh": {k: int(v) for k, v in eng.mesh.shape.items()},
            "spmd": bool(eng._spmd),
            "tokens": {str(r.rid): [int(t) for t in r.tokens]
                       for r in eng.requests},
            "fallbacks": {k: str(v) for k, v in rep["fallbacks"].items()},
            "shard_fallbacks": dict(ws["shard_fallbacks"]),
            "shards": int(ws["shards"]),
            "kv_shards": int(eng.kv.shards) if SPEC["paged"] else 1,
            "dev_sparse": int(ws["device_sparse_bytes_per_step"]),
            "tot_sparse": int(ws["sparse_bytes_per_step"]),
            "sharded_entries": int(nsh),
            "packed_dev": int(sh_dev),
            "packed_tot": int(sh_tot),
        }


    print(json.dumps({str(mp): run(mp) for mp in SPEC["mps"]}))
""")

# Pairwise coverage of the full matrix: both archs, mp in {1, 2, 4},
# sparsity in {0, 0.75}, contiguous vs paged KV, legacy decode vs
# chunked prefill.  Paged scenarios use num_slots=8 so every data-axis
# extent (8/mp) divides the slot count and the KV pool actually shards.
SCENARIOS = {
    "olmo-sparse-paged-prefill": dict(
        arch="olmo-1b", sparsity=0.75, paged=True, prefill_chunk=8,
        num_slots=8, mps=[1, 2, 4]),
    "olmo-dense-contig-decode": dict(
        arch="olmo-1b", sparsity=0.0, paged=False, prefill_chunk=0,
        num_slots=4, mps=[2]),
    "granite-sparse-contig-decode": dict(
        arch="granite-moe-3b-a800m", sparsity=0.75, paged=False,
        prefill_chunk=0, num_slots=4, mps=[4]),
    "granite-dense-paged-prefill": dict(
        arch="granite-moe-3b-a800m", sparsity=0.0, paged=True,
        prefill_chunk=8, num_slots=8, mps=[2]),
}

_CACHE = {}


def _worker(name, ndev, mps):
    key = (name, ndev, tuple(mps))
    if key in _CACHE:
        return _CACHE[key]
    spec = dict(SCENARIOS[name], mps=list(mps))
    script = (_WORKER.replace("%NDEV%", str(ndev))
              .replace("%SPEC%", json.dumps(spec)))
    env = {**os.environ, "PYTHONPATH": "src"}
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True,
                          timeout=TIMEOUT, env=env,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, \
        f"{name} (ndev={ndev}) failed:\n{proc.stderr[-3000:]}"
    _CACHE[key] = json.loads(proc.stdout.strip().splitlines()[-1])
    return _CACHE[key]


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_sharded_serving_matches_single_device(name):
    spec = SCENARIOS[name]
    oracle = _worker(name, 1, [1])["1"]
    assert oracle["spmd"] is False
    assert oracle["shards"] == 1

    runs = _worker(name, 8, spec["mps"])
    for mp_s, r in runs.items():
        mp = int(mp_s)
        ctx = f"{name} mp={mp}"
        assert r["spmd"] is True, ctx
        assert r["mesh"] == {"data": 8 // mp, "model": mp}, ctx

        # the whole point: tokens are bit-identical to one device
        assert r["tokens"] == oracle["tokens"], ctx

        # no unexpected fallbacks, and none blamed on model_parallel
        assert set(r["fallbacks"]) == set(oracle["fallbacks"]), ctx
        for reason in r["fallbacks"].values():
            assert "model_parallel" not in reason, (ctx, reason)

        assert r["shards"] == mp, ctx
        if spec["paged"]:
            # KV pools shard over the data axis (8 // mp extents)
            assert r["kv_shards"] == 8 // mp, ctx

        if mp > 1:
            # every TP-eligible tensor actually sharded on these shapes
            assert r["shard_fallbacks"] == {}, ctx
            assert r["sharded_entries"] > 0, ctx
            # per-device packed bytes == total / mp, floor-div per tensor
            assert r["packed_dev"] * mp <= r["packed_tot"], ctx
            assert (r["packed_tot"] - r["packed_dev"] * mp
                    < mp * r["sharded_entries"]), ctx
            assert r["dev_sparse"] < r["tot_sparse"], ctx
        else:
            assert r["dev_sparse"] == r["tot_sparse"], ctx


# ------------------- typed degradation, no crash (single device) ----------


def test_indivisible_dims_record_typed_shard_reasons():
    """A shard count that divides nothing still packs — every eligible
    tensor keeps its unsharded tile and carries a typed shard reason."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models.model import init_params
    from repro.serve import pack_model

    cfg = get_smoke_config("olmo-1b")        # d_model=64: 3 divides nothing
    params = init_params(jax.random.PRNGKey(0), cfg)
    pm = pack_model(params, shards=3)
    sharded = [e for e in pm.manifest if e.shard is not None]
    reasons = {e.path: e.shard_reason for e in pm.manifest
               if e.shard_reason}
    assert sharded == [], sharded
    assert reasons, "expected typed per-tensor shard fallbacks"
    for path, reason in reasons.items():
        assert reason.startswith("shard:"), (path, reason)
        assert "replicated" in reason, (path, reason)
    # packing itself is unaffected: the entries still packed
    assert any(e.packed for e in pm.manifest)
    rep = pm.stream_report()
    assert rep["shards"] == 3
    assert set(rep["shard_fallbacks"]) == set(reasons)
    # nothing sharded -> device bytes degenerate to the totals
    assert rep["device_sparse_bytes_per_step"] == rep["sparse_bytes_per_step"]


def test_indivisible_vocab_keeps_head_replicated():
    import jax
    from repro.configs import get_smoke_config
    from repro.models.model import init_params
    from repro.serve.engine import pack_lm_head

    cfg = get_smoke_config("olmo-1b")        # vocab=256: 3 doesn't divide
    params = init_params(jax.random.PRNGKey(1), cfg)
    bw = pack_lm_head(params, cfg, sparsity=0.5, shards=3)
    assert bw is not None and bw.shard is None
    sharded = pack_lm_head(params, cfg, sparsity=0.5, shards=4)
    assert sharded is not None and sharded.shard == ("col", 4)
