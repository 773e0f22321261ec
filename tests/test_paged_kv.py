"""Paged KV cache tests: block allocator bookkeeping, prefix sharing with
copy-on-write, block-mapped decode kernels, the ``kv.*`` verify family,
and dense-vs-paged engine parity."""
from __future__ import annotations

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RuntimeConfig
from repro.core import verify
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention import ref as attn_ref
from repro.launch.engine import BlockAllocator, Engine, PrefixCache, Request
from repro.launch.serve import ServeConfig, Server
from repro.models import lm

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"


@pytest.fixture(scope="module")
def paged_server():
    # max_len 24 / block size 4 -> 6 blocks per worst-case request
    return Server(ServeConfig(arch="deepseek-7b", batch=4, prompt_len=14,
                              new_tokens=6, max_len=24))


def _shared_prefix_queue(vocab: int, n: int = 12, prefix_len: int = 8,
                         max_new: int = 4, seed: int = 0) -> list[Request]:
    rng = np.random.default_rng(seed)
    common = rng.integers(1, vocab, prefix_len).tolist()
    reqs = []
    for i in range(n):
        tail = rng.integers(1, vocab, int(rng.integers(0, 4))).tolist()
        prompt = (common + tail) if i % 3 else tail
        reqs.append(Request(request_id=i, prompt=prompt,
                            max_new_tokens=max_new))
    return reqs


# ---------------------------------------------------------------------------
# host-side allocator / prefix cache
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_random_ops_preserve_invariants(self):
        """Property loop: any interleaving of alloc/share/release keeps the
        free list, refcounts and stored-token accounting consistent."""
        rng = np.random.default_rng(7)
        alloc = BlockAllocator(num_blocks=12, block_size=4)
        held: list[int] = []        # one entry per reference we own
        for _ in range(2000):
            op = rng.integers(0, 4)
            if op == 0 and alloc.n_free:
                b = alloc.alloc()
                alloc.note_fill(b, int(rng.integers(0, 5)))
                held.append(b)
            elif op == 1 and held:
                b = held[int(rng.integers(0, len(held)))]
                alloc.share(b)
                held.append(b)
            elif op >= 2 and held:
                b = held.pop(int(rng.integers(0, len(held))))
                alloc.release(b)
            assert alloc.n_free + alloc.in_use == alloc.num_blocks
            assert all(r >= 0 for r in alloc.refcount)
            free = set(alloc.free_blocks())
            assert all(alloc.refcount[b] == 0 for b in free)
            want = {b: held.count(b) for b in set(held)}
            assert all(alloc.refcount[b] == c for b, c in want.items())
            assert alloc.stored == sum(alloc.filled[b] for b in set(held))
        for b in list(held):
            alloc.release(b)
        assert alloc.n_free == alloc.num_blocks
        assert alloc.stored == 0

    def test_exhaustion_raises(self):
        alloc = BlockAllocator(num_blocks=2, block_size=4)
        alloc.alloc(), alloc.alloc()
        with pytest.raises(RuntimeError, match="exhausted"):
            alloc.alloc()

    def test_release_returns_block_only_at_zero_refs(self):
        alloc = BlockAllocator(num_blocks=2, block_size=4)
        b = alloc.alloc()
        alloc.share(b)
        alloc.release(b)
        assert b not in alloc.free_blocks()     # the cache still holds it
        alloc.release(b)
        assert b in alloc.free_blocks()


class TestPrefixCache:
    def _cache(self, num_blocks=8, bs=4):
        alloc = BlockAllocator(num_blocks, bs)
        return alloc, PrefixCache(alloc)

    def test_full_chain_and_partial_roundtrip(self):
        alloc, pc = self._cache()
        prompt = np.arange(11, dtype=np.int32)      # 2 full blocks + 3 tail
        b0, b1, b2 = alloc.alloc(), alloc.alloc(), alloc.alloc()
        k = pc.register_full(b"\x00" * 16, prompt[0:4], b0)
        k = pc.register_full(k, prompt[4:8], b1)
        pc.register_partial(k, prompt[8:11], b2)
        fulls, _, partial = pc.lookup(prompt)
        assert fulls == [b0, b1]
        assert partial == (b2, 3)
        # divergent tail: the full chain still hits, the partial does not
        other = prompt.copy()
        other[9] = 99
        fulls2, _, partial2 = pc.lookup(other)
        assert fulls2 == [b0, b1] and partial2 is None
        # a different first block kills the whole chain
        fulls3, _, _ = pc.lookup(np.asarray([99, 1, 2, 3, 4], np.int32))
        assert fulls3 == []

    def test_partial_never_satisfies_full_walk(self):
        """A registered sub-block tail is keyed apart from full blocks:
        a prompt whose next *full* block happens to start with those same
        tokens must not map the partial block as a full one."""
        alloc, pc = self._cache()
        b = alloc.alloc()
        pc.register_partial(b"\x00" * 16, np.asarray([1, 2, 3], np.int32), b)
        fulls, _, _ = pc.lookup(np.asarray([1, 2, 3, 4, 5], np.int32))
        assert fulls == []

    def test_evict_skips_blocks_live_slots_map(self):
        alloc, pc = self._cache(num_blocks=2)
        b0, b1 = alloc.alloc(), alloc.alloc()
        k = pc.register_full(b"\x00" * 16, np.arange(4, dtype=np.int32), b0)
        pc.register_full(k, np.arange(4, 8, dtype=np.int32), b1)
        alloc.release(b1)           # cache-only now; b0 still slot-mapped
        assert pc.evict(2) == 1     # only b1 is evictable
        assert b1 in alloc.free_blocks()
        assert alloc.refcount[b0] == 2
        pc.clear()
        alloc.release(b0)
        assert alloc.n_free == alloc.num_blocks


# ---------------------------------------------------------------------------
# block-mapped decode: kernel vs reference, freed-slot convention
# ---------------------------------------------------------------------------

class TestPagedDecode:
    def _case(self, lengths, seed=0):
        rng = np.random.default_rng(seed)
        B, H, G, D, bs, N, MB = len(lengths), 4, 2, 8, 4, 16, 3
        q = jnp.asarray(rng.standard_normal((B, H, 1, D)), jnp.float32)
        k_pool = jnp.asarray(rng.standard_normal((N, G, bs, D)), jnp.float32)
        v_pool = jnp.asarray(rng.standard_normal((N, G, bs, D)), jnp.float32)
        table = jnp.asarray(rng.permutation(N)[:B * MB].reshape(B, MB),
                            jnp.int32)
        lens = jnp.asarray(lengths, jnp.int32)
        return q, k_pool, v_pool, table, lens

    @pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                            (jnp.bfloat16, 1e-2)],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("h, g", [(4, 4), (4, 2), (8, 2)],
                             ids=["mha", "gqa2", "gqa4"])
    def test_kernel_matches_ref_on_ragged_lengths(self, h, g, dtype, tol):
        """Lengths at every block edge (empty, one position, one short of
        a block, a block, one past it, the whole table), with each slot's
        table tail past its live range holding the allocator's sentinel
        ``n``.  The pools come in the query's dtype, as the serving path
        casts them; the reference runs in float32 on the same values."""
        rng = np.random.default_rng(h * 10 + g)
        bs, mb, n, d = 4, 3, 16, 8
        lengths = [0, 1, bs - 1, bs, bs + 1, mb * bs]
        b = len(lengths)
        q = jnp.asarray(rng.standard_normal((b, h, 1, d)), dtype)
        kp = jnp.asarray(rng.standard_normal((n, g, bs, d)), dtype)
        vp = jnp.asarray(rng.standard_normal((n, g, bs, d)), dtype)
        free = iter(rng.permutation(n).tolist())
        table = np.full((b, mb), n, np.int32)
        for i, length in enumerate(lengths):
            for j in range(-(-length // bs)):
                table[i, j] = next(free)
        tbl, lens = jnp.asarray(table), jnp.asarray(lengths, jnp.int32)
        out_k = attn_ops.paged_flash_decode(q, kp, vp, tbl, lens)
        assert out_k.shape == q.shape and out_k.dtype == dtype
        f32 = [x.astype(jnp.float32) for x in (q, kp, vp)]
        out_r = attn_ref.paged_decode_ref(*f32, tbl, lens)
        out_k = np.asarray(out_k.astype(jnp.float32))
        assert (out_k[0] == 0.0).all()
        np.testing.assert_allclose(out_k, np.asarray(out_r),
                                   atol=tol, rtol=tol)

    def test_gather_matches_dense_reference_bitwise(self):
        """The xla-mode paged path is a gather + the dense reference — on
        identical logical contents it must be bit-identical to the dense
        reference (this is what makes paged/dense greedy parity exact)."""
        q, kp, vp, tbl, lens = self._case([9, 4, 1, 12])
        k_dense = attn_ref.gather_paged(kp, tbl)
        v_dense = attn_ref.gather_paged(vp, tbl)
        out_p = attn_ref.paged_decode_ref(q, kp, vp, tbl, lens)
        out_d = attn_ref.attention_ref(q, k_dense, v_dense, causal=False,
                                       lengths=lens)
        assert np.array_equal(np.asarray(out_p), np.asarray(out_d))

    def test_zero_length_slot_emits_exact_zeros(self):
        """Freed-slot regression: a ``lengths == 0`` row (reset slot whose
        table row points anywhere) must emit exactly zero from both the
        kernel and the reference — not NaN, not a stale-pool average."""
        q, kp, vp, tbl, lens = self._case([0, 7, 0])
        for out in (attn_ops.paged_flash_decode(q, kp, vp, tbl, lens),
                    attn_ref.paged_decode_ref(q, kp, vp, tbl, lens)):
            out = np.asarray(out)
            assert (out[0] == 0.0).all() and (out[2] == 0.0).all()
            assert np.isfinite(out).all()
            assert np.abs(out[1]).max() > 0.0


# ---------------------------------------------------------------------------
# kv.* invariant family
# ---------------------------------------------------------------------------

def _clean_state() -> verify.BlockTableState:
    return verify.BlockTableState(
        num_blocks=8, block_size=4,
        refcounts=(2, 1, 1, 0, 0, 0, 0, 1),
        free=(3, 4, 5, 6),
        tables=((0, 1), (0, 2)),
        lengths=(8, 7),
        cached=(7,),
        writers=(1, 2))


class TestBlockTableInvariants:
    def test_clean_state_has_no_findings(self):
        assert verify.check_block_tables(_clean_state()) == []

    @pytest.mark.parametrize("mutate,invariant", [
        (dict(tables=((0, 99), (0, 2))), "kv.block-out-of-bounds"),
        (dict(lengths=(9, 7)), "kv.length-uncovered"),
        (dict(refcounts=(1, 1, 1, 0, 0, 0, 0, 1)), "kv.refcount-mismatch"),
        (dict(writers=(0, 1, 2)), "kv.shared-writable"),
        (dict(free=(1, 3, 4, 5, 6),
              refcounts=(2, 0, 1, 0, 0, 0, 0, 1)), "kv.freed-reachable"),
    ])
    def test_seeded_mutants_are_caught(self, mutate, invariant):
        state = dataclasses.replace(_clean_state(), **mutate)
        found = verify.check_block_tables(state)
        assert any(f.invariant == invariant and f.severity == "error"
                   for f in found), found

    def test_strict_mode_raises(self):
        state = dataclasses.replace(_clean_state(), writers=(0, 1, 2))
        with pytest.raises(verify.VerifyError, match="kv.shared-writable"):
            verify.enforce(verify.check_block_tables(state), "strict")


# ---------------------------------------------------------------------------
# engine: parity, sharing, copy-on-write, leak freedom, oversubscription
# ---------------------------------------------------------------------------

class TestPagedEngine:
    def test_paged_matches_dense_on_ragged_queue(self, paged_server):
        """The tentpole parity contract: greedy completions through the
        paged layout are token-identical to dense on a ragged queue with
        shared-prefix traffic, while prefix sharing prefills strictly
        fewer tokens."""
        reqs = _shared_prefix_queue(paged_server.cfg.vocab_size)
        e_d = paged_server.engine(prefill_chunk=4)
        out_d = e_d.run(reqs)
        e_p = paged_server.engine(prefill_chunk=4, kv_layout="paged",
                                  kv_block_size=4, verify_mode="strict")
        out_p = e_p.run(reqs)
        for a, b in zip(out_d, out_p):
            assert a.status == b.status == "ok"
            assert np.array_equal(a.tokens, b.tokens)
        sp = e_p.last_stats
        assert sp.prefix_hit_tokens > 0
        assert sp.prefill_tokens < e_d.last_stats.prefill_tokens
        assert sp.prefill_tokens + sp.prefix_hit_tokens \
            == e_d.last_stats.prefill_tokens

    def test_cow_fork_on_shared_prefix_divergence(self, paged_server):
        """Two identical prompts served serially: the second maps the
        first's registered blocks, and its first KV write lands in a
        shared block — the write barrier must fork it, not corrupt the
        cache entry."""
        vocab = paged_server.cfg.vocab_size
        prompt = np.random.default_rng(3).integers(1, vocab, 8)
        reqs = [Request(request_id=i, prompt=prompt, max_new_tokens=3)
                for i in range(2)]
        e = paged_server.engine(slots=1, prefill_chunk=4,
                                kv_layout="paged", kv_block_size=4,
                                verify_mode="strict")
        out = e.run(reqs)
        assert all(c.status == "ok" for c in out)
        assert np.array_equal(out[0].tokens, out[1].tokens)
        assert e.last_stats.cow_forks >= 1
        assert e.last_stats.prefix_hit_tokens == 7   # plen-1 cap

    def test_block_counters_match_served_lengths(self, paged_server):
        """``paged_blocks_live`` counts the blocks each token-consuming
        lane attends to, per model evaluation: a request of prompt ``p``
        and ``m`` new tokens consumes ``p + m - 1`` tokens, at lengths
        ``1 .. p + m - 1``.  ``paged_blocks_grid`` counts the table width
        for each; both reach ``report()``."""
        rng = np.random.default_rng(11)
        plens, news = [5, 3, 7, 1, 9], [4, 6, 2, 5, 3]
        reqs = [Request(request_id=i,
                        prompt=rng.integers(1, paged_server.cfg.vocab_size,
                                            p).tolist(),
                        max_new_tokens=m)
                for i, (p, m) in enumerate(zip(plens, news))]
        e = paged_server.engine(slots=2, prefill_chunk=4, kv_layout="paged",
                                kv_block_size=4, prefix_sharing=False,
                                verify_mode="strict")
        out = e.run(reqs)
        assert all(c.status == "ok" for c in out)
        bs, mb = 4, 24 // 4
        live = sum(-(-x // bs) for p, m in zip(plens, news)
                   for x in range(1, p + m))
        grid = sum(p + m - 1 for p, m in zip(plens, news)) * mb
        got = e.report()["dispatch"]
        assert got["paged_blocks_live"] == live
        assert got["paged_blocks_grid"] == grid
        assert live <= grid

    def test_no_block_leak_after_run(self, paged_server):
        reqs = _shared_prefix_queue(paged_server.cfg.vocab_size, n=9,
                                    seed=5)
        e = paged_server.engine(prefill_chunk=4, kv_layout="paged",
                                kv_block_size=4, verify_mode="strict")
        e.run(reqs)
        alloc = e.last_allocator
        assert alloc.n_free == alloc.num_blocks
        assert all(r == 0 for r in alloc.refcount)
        assert alloc.stored == 0

    def test_oversubscribed_pool_serves_whole_queue(self, paged_server):
        """Acceptance: a pool half the dense footprint (12 blocks * 4 =
        48 token slots vs slots * max_len = 96) serves a queue whose total
        prompt+decode footprint exceeds even the dense capacity —
        admission queues on free blocks instead of failing — with greedy
        parity against the dense engine held throughout."""
        reqs = _shared_prefix_queue(paged_server.cfg.vocab_size)
        footprint = sum(len(r.prompt) + r.max_new_tokens for r in reqs)
        assert footprint > 4 * 24           # exceeds dense slots * max_len
        e_p = paged_server.engine(prefill_chunk=4, kv_layout="paged",
                                  kv_block_size=4, kv_num_blocks=12,
                                  verify_mode="strict")
        out_p = e_p.run(reqs)
        assert all(c.status == "ok" for c in out_p)
        e_d = paged_server.engine(prefill_chunk=4)
        out_d = e_d.run(reqs)
        for a, b in zip(out_d, out_p):
            assert np.array_equal(a.tokens, b.tokens)
        sp = e_p.last_stats
        assert sp.blocks_in_use <= 12
        assert 0.0 < sp.kv_block_utilization <= 1.0

    def test_mamba_family_disables_prefix_sharing(self):
        srv = Server(ServeConfig(arch="mamba2-2.7b", batch=2, prompt_len=6,
                                 new_tokens=4, max_len=16))
        e = srv.engine(kv_layout="paged", kv_block_size=4)
        assert e.prefix_sharing is False
        prompt = np.random.default_rng(0).integers(1, srv.cfg.vocab_size, 6)
        reqs = [Request(request_id=i, prompt=prompt, max_new_tokens=2)
                for i in range(2)]
        out_p = e.run(reqs)
        assert e.last_stats.prefix_hit_tokens == 0
        out_d = srv.engine().run(reqs)
        for a, b in zip(out_d, out_p):
            assert a.status == b.status == "ok"
            assert np.array_equal(a.tokens, b.tokens)

    def test_pool_smaller_than_one_request_rejected(self, paged_server):
        with pytest.raises(ValueError, match="kv_num_blocks"):
            paged_server.engine(kv_layout="paged", kv_block_size=4,
                                kv_num_blocks=2)


# ---------------------------------------------------------------------------
# the decode cache in the model's dtype
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("arch", ["deepseek-7b", "granite-4.0-h-small"],
                         ids=["dense", "hybrid"])
def test_engine_cache_leaves_take_the_model_dtype(arch, dtype):
    """The cache the engine builds (for a run and for its mesh plan): K/V,
    paged or dense, and the Mamba conv window in the model's dtype; the
    SSM state float32; lengths int32."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype=dtype)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    want = {"k": dtype, "v": dtype, "k_pool": dtype, "v_pool": dtype,
            "conv": dtype, "state": "float32", "length": "int32"}
    for layout in ("paged", "dense"):
        rt = RuntimeConfig(mode="xla", kv_layout=layout, kv_block_size=4)
        eng = Engine(cfg, params, rt, slots=2, max_len=16)
        got = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                jax.eval_shape(eng.init_cache)):
            got.setdefault(path[-1].name, set()).add(str(leaf.dtype))
        assert got == {k: {want[k]} for k in got}, (layout, got)
        kv = {"k_pool", "v_pool"} if layout == "paged" else {"k", "v"}
        assert kv <= set(got)
        assert ({"conv", "state"} <= set(got)) == (arch != "deepseek-7b")


def _bench_case(name: str, monkeypatch):
    """The benchmark's CPU-size ``name`` configuration at bfloat16: the
    program's config and the benchmark's seeded weights in its tree."""
    monkeypatch.syspath_prepend(str(BENCH))
    import harness

    cfg = json.loads((BENCH.parent / "tests" / "bench" / "tiny" / "configs"
                      / f"{name}.json").read_text())
    cfg["torch_dtype"] = "bfloat16"
    ref = harness.load_module(BENCH / "configs" / f"{name}.py")
    w = jax.jit(lambda k: ref.init(cfg, k))(jax.random.PRNGKey(5))
    if name == "deepseek-7b":
        drv = harness.load_module(BENCH / "drivers" / "serve.py")
        return cfg, drv.program_config(cfg), drv.program_params(w)
    drv = harness.load_module(BENCH / "drivers" / "serve_hybrid.py")
    return cfg, drv.program_config(cfg), drv.program_params(w, cfg)


@pytest.mark.parametrize("name", ["deepseek-7b", "granite-4.0-h-small"])
def test_model_dtype_cache_serves_the_same_logits(name, monkeypatch):
    """A bfloat16 model writes only bfloat16 values into its K/V pools and
    conv window, so storing them in bfloat16 instead of float32 gives
    bit-identical logits: 40 tokens through a paged cache and a shuffled
    block table, in brainslug mode (the Pallas paged-decode kernel)."""
    cfg, mc, params = _bench_case(name, monkeypatch)
    assert mc.dtype == "bfloat16"
    bs, n, slots, mb = 16, 40, 2, 3
    rt = RuntimeConfig(mode="brainslug", kv_layout="paged", kv_block_size=bs)
    table = jnp.asarray(np.random.default_rng(1).permutation(
        slots * mb).reshape(slots, mb), jnp.int32)
    tokens = np.random.default_rng(2).integers(0, cfg["vocab_size"],
                                               (n, slots, 1))
    step = jax.jit(lambda c, t: lm.decode_step(params, c, t, mc, rt,
                                               block_tables=table)[:2])
    logits = {}
    for dtype in (jnp.float32, None):
        cache = lm.init_decode_cache(mc, slots, mb * bs, dtype=dtype,
                                     kv_layout="paged",
                                     kv_num_blocks=slots * mb,
                                     kv_block_size=bs)
        got = []
        for t in tokens:
            out, cache = step(cache, jnp.asarray(t, jnp.int32))
            got.append(np.asarray(out.astype(jnp.float32)))
        logits[dtype] = np.stack(got)
        pools = {x.dtype for path, x in
                 jax.tree_util.tree_leaves_with_path(cache)
                 if path[-1].name in ("k_pool", "v_pool")}
        assert pools == {jnp.dtype(dtype or jnp.bfloat16)}
    assert np.isfinite(logits[None]).all()
    assert np.array_equal(logits[jnp.float32], logits[None])
