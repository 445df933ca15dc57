"""Block-paged KV: pool refcounting/CoW invariants + engine exactness.

The satellite contract from r13: CoW forks on first write, release to
zero returns blocks to the free pool, a recycled slot never reads a
stale prefix block, and block OOM rejects admission cleanly (no torn
state). Plus the tentpole's exactness contract: the paged engine —
cached prefix or not — stays bit-identical to solo ``generate_fused``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.models import LlamaConfig, init_params
from kubeflow_rm_tpu.models.generate import (
    ContinuousBatchingEngine,
    generate_fused,
)
from kubeflow_rm_tpu.models.paging import (
    RESERVED_BLOCKS,
    BlockPool,
    prefix_keys,
)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


# -- host-side pool invariants (no device work) --------------------------


def test_prefix_keys_chain_and_divergence():
    """Keys digest the whole prefix, so chains diverge at (not after)
    the first differing block; a partial tail gets its own key."""
    a = prefix_keys([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
    assert [c for c, _ in a] == [4, 8, 9]
    b = prefix_keys([1, 2, 3, 4, 5, 6, 7, 99, 9], 4)
    assert a[0][1] == b[0][1]          # same first block
    assert a[1][1] != b[1][1]          # diverged second block
    assert a[2][1] != b[2][1]          # ...and everything after
    # block-aligned prompt: no partial key
    assert [c for c, _ in prefix_keys([1, 2, 3, 4], 4)] == [4]


def test_pool_alloc_release_to_zero_returns_blocks():
    pool = BlockPool(RESERVED_BLOCKS + 4, 8)
    assert pool.usable_blocks == 4 and pool.available() == 4
    got = pool.alloc(3)
    assert len(got) == 3 and pool.available() == 1
    assert all(pool.ref_of(b) == 1 for b in got)
    pool.decref(got)
    # unregistered blocks go straight back to the free list
    assert pool.available() == 4 and pool.free_count() == 4
    # and can be handed out again
    assert len(pool.alloc(4)) == 4


def test_pool_registered_blocks_are_retained_then_evicted():
    pool = BlockPool(RESERVED_BLOCKS + 3, 8)
    keys = prefix_keys([1, 2, 3, 4, 5, 6, 7, 8], 4)
    (b0, b1) = pool.alloc(2)
    pool.register(keys[0][1], b0)
    pool.register(keys[1][1], b1)
    pool.decref([b0, b1])
    # ref 0 but registered: retained as prefix cache, not freed
    assert pool.free_count() == 1 and pool.evictable_count() == 2
    assert pool.lookup_chain(keys) == [b0, b1]
    # an alloc that outgrows the free list evicts oldest-first — and
    # eviction unregisters, so the stale key can never resolve again
    got = pool.alloc(2)
    assert len(got) == 2 and pool.evictions == 1
    assert pool.lookup_chain(keys) == []   # chain broken at its head


def test_pool_alloc_is_atomic_on_oom():
    pool = BlockPool(RESERVED_BLOCKS + 3, 8)
    first = pool.alloc(2)
    before = (pool.free_count(), pool.available(),
              {b: pool.ref_of(b) for b in first})
    assert pool.alloc(2) is None           # only 1 left
    after = (pool.free_count(), pool.available(),
             {b: pool.ref_of(b) for b in first})
    assert before == after                 # nothing torn
    assert pool.alloc_failures == 1
    assert len(pool.alloc(1)) == 1         # the remainder still works


def test_pool_refcount_underflow_raises():
    pool = BlockPool(RESERVED_BLOCKS + 2, 8)
    (b,) = pool.alloc(1)
    pool.decref([b])
    with pytest.raises(RuntimeError, match="below zero"):
        pool.decref([b])


def test_pool_incref_pins_against_eviction():
    """The admission ordering hazard: a pinned (incref'd) chain hit
    must never be recycled by a following alloc."""
    pool = BlockPool(RESERVED_BLOCKS + 2, 8)
    keys = prefix_keys([1, 2, 3, 4], 4)
    (b,) = pool.alloc(1)
    pool.register(keys[0][1], b)
    pool.decref([b])                       # retained, evictable
    pool.incref([b])                       # ...until pinned
    assert pool.alloc(2) is None           # would need to evict b
    assert pool.lookup_chain(keys) == [b]  # still intact
    pool.decref([b])


# -- engine-level contracts ----------------------------------------------


def _solo(params, cfg, prompt, budget, slot_len=32):
    ref = generate_fused(params, cfg, jnp.asarray([prompt], jnp.int32),
                         max_new_tokens=budget, max_len=slot_len)
    return np.asarray(ref)[0, len(prompt):].tolist()


def test_paged_engine_prefix_hit_is_bit_identical(model):
    """Identical prompts take the cached-prefix path (adopt + CoW
    fork) and must still decode bit-identically to solo fused —
    the tentpole acceptance bar."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=32,
                                   block_size=4)
    prompt = [5, 9, 2, 7, 1, 1, 3]          # 7 = non-block-aligned
    sibling = prompt + [8]                  # shares one full block
    reqs = [eng.submit(list(p), max_new_tokens=b)
            for p, b in ((prompt, 6), (prompt, 6), (sibling, 5),
                         (prompt, 6))]
    eng.run()
    for r, (p, b) in zip(reqs, ((prompt, 6), (prompt, 6),
                                (sibling, 5), (prompt, 6))):
        assert r.tokens == _solo(params, cfg, list(p), b)
    st = eng.stats()
    assert st["prefix_hit_tokens"] > 0 and st["prefix_hit_ratio"] > 0
    # repeats of a non-aligned prompt must have forked, not shared,
    # their write block
    assert st["cow_forks"] >= 1


def test_cow_fork_on_first_write_preserves_source(model):
    """The fork source must be byte-identical after the forker decodes
    into its copy — shared blocks are immutable."""
    from kubeflow_rm_tpu.models.paging import gather_slot_strip

    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=32,
                                   block_size=4)
    prompt = [5, 9, 2, 7, 1, 1]             # 6: partial second block
    r0 = eng.submit(list(prompt), max_new_tokens=2)
    eng.run()                               # registers the chain
    src_blocks = list(eng.pool.lookup_chain(
        prefix_keys(prompt, 4)))
    assert src_blocks
    before = np.asarray(eng.cache.k[:, src_blocks])

    r1 = eng.submit(list(prompt), max_new_tokens=6)
    eng.run()                               # adopts + forks + decodes
    after = np.asarray(eng.cache.k[:, src_blocks])
    np.testing.assert_array_equal(before, after)
    assert eng.pool.cow_forks >= 1
    assert r0.tokens == _solo(params, cfg, prompt, 2)
    assert r1.tokens == _solo(params, cfg, prompt, 6)
    # sanity on the gather debug view: slot strips stay disjoint
    assert gather_slot_strip(eng.cache, 0)[2].shape == (32,)


def test_recycled_slot_never_reads_stale_prefix(model):
    """Evict a registered chain by pressure, then replay the original
    prompt: the chain must MISS (re-prefill) and the output must still
    be exact — a stale lookup would decode garbage."""
    cfg, params = model
    # pool sized so one in-flight request + a little headroom: the
    # second prompt's allocation must evict the first's retained chain
    eng = ContinuousBatchingEngine(params, cfg, slots=1, slot_len=32,
                                   block_size=4,
                                   num_blocks=RESERVED_BLOCKS + 5)
    pa = [5, 9, 2, 7, 1, 1, 3]
    pb = [11, 4, 6, 2, 9, 9, 1, 3, 5, 8, 2, 7]
    ra = eng.submit(list(pa), max_new_tokens=8)       # needs 4 blocks
    eng.run()
    assert eng.pool.lookup_chain(prefix_keys(pa, 4))  # retained
    rb = eng.submit(list(pb), max_new_tokens=8)       # needs all 5
    eng.run()
    assert eng.pool.evictions >= 1
    assert eng.pool.lookup_chain(prefix_keys(pa, 4)) == []
    ra2 = eng.submit(list(pa), max_new_tokens=8)
    hit_before = eng.stats()["prefix_hit_tokens"]
    eng.run()
    assert eng.stats()["prefix_hit_tokens"] == hit_before  # true miss
    assert ra.tokens == ra2.tokens == _solo(params, cfg, pa, 8)
    assert rb.tokens == _solo(params, cfg, pb, 8)


def test_block_oom_rejects_cleanly_then_recovers(model):
    """Transient block exhaustion: the head request waits (front of
    its queue, pool untouched) and admits once a slot retires; a
    request that could NEVER fit is refused at submit."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=32,
                                   block_size=4,
                                   num_blocks=RESERVED_BLOCKS + 5)
    with pytest.raises(ValueError, match="blocks"):
        # fits the slot (bucket 8 + 24 = 32) but needs 8 > 5 blocks
        eng.submit([1] * 8, max_new_tokens=24)
    r1 = eng.submit([5, 9, 2, 7, 1, 1, 3, 4], max_new_tokens=12)
    r2 = eng.submit([11, 4, 6, 2, 9, 9, 1, 3], max_new_tokens=12)
    eng.step()
    assert r1.admitted_step is not None     # r1 holds all 5 blocks
    assert r2.admitted_step is None         # r2 needs 5: clean wait
    assert eng.pool.alloc_failures >= 1
    eng.run()
    assert r1.tokens == _solo(params, cfg, [5, 9, 2, 7, 1, 1, 3, 4], 12)
    assert r2.tokens == _solo(params, cfg, [11, 4, 6, 2, 9, 9, 1, 3], 12)
    # all blocks drained back: nothing leaked across the OOM bounce
    assert (eng.pool.available() == eng.pool.usable_blocks)


def test_slo_class_weighted_admission(model):
    """With one slot and all three queues backed up, admissions drain
    by weighted share — interactive dominates early but nothing
    starves."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, slots=1, slot_len=16,
                                   block_size=4)
    with pytest.raises(ValueError, match="slo_class"):
        eng.submit([1, 2], max_new_tokens=1, slo_class="platinum")
    reqs = []
    for c in ("interactive", "batch", "best_effort"):
        reqs += [eng.submit([3, 5, 7], max_new_tokens=2, slo_class=c)
                 for _ in range(12)]
    eng.run()
    order = [r.slo_class for r in
             sorted(reqs, key=lambda r: r.admitted_step)]
    head = order[:12]
    assert head.count("interactive") >= 7      # ~8/12 by weight
    assert head.count("batch") >= 2
    assert head.count("best_effort") >= 1      # no starvation
    st = eng.stats()
    assert st["admitted_by_class"] == {"interactive": 12, "batch": 12,
                                       "best_effort": 12}
    assert st["queue_depth_by_class"] == {"interactive": 0, "batch": 0,
                                          "best_effort": 0}


def test_evict_queued_returns_unadmitted_only(model):
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, slots=1, slot_len=16,
                                   block_size=4)
    r1 = eng.submit([3, 5, 7], max_new_tokens=4)
    r2 = eng.submit([2, 4], max_new_tokens=4, slo_class="batch")
    eng.step()                              # r1 takes the slot
    evicted = eng.evict_queued()
    assert evicted == [r2] and eng.queue_depth == 0
    eng.run()                               # r1 still finishes here
    assert r1.done and not r2.done
    assert r1.tokens == _solo(params, cfg, [3, 5, 7], 4, slot_len=16)


# -- the decode step's structure and its counter ---------------------------


def test_decode_step_never_builds_a_strip_of_the_whole_cache():
    """Compile ``paged_decode_step`` at eight layers and hold its
    temporaries under three layers' worth of K and V strips. A step
    that gathers every slot's whole strip for every layer ahead of the
    scan (as it did until PR 31) holds all eight at least once — it
    read eighteen layers' worth here; a step that reads the pool
    through the block table holds one layer's. The pool is kept small
    against the strips so that its own copies, which the CPU's scatter
    makes, do not blur the line."""
    from kubeflow_rm_tpu.models import paging

    cfg = LlamaConfig.tiny(n_layers=8)
    slots, slot_len, block = 4, 256, 4
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: paging.init_paged_cache(
        cfg, slots, slot_len, RESERVED_BLOCKS + 8, block))
    compiled = paging.paged_decode_step.lower(
        params, cfg, cache, jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_)).compile()
    one_layer = (2 * slots * slot_len * cfg.n_kv_heads * cfg.head_dim
                 * jnp.dtype(cfg.dtype).itemsize)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * one_layer


def test_kv_blocks_read_total_counts_what_the_lengths_say(model):
    """Every decode step adds, for each live slot, the blocks its
    strip reaches with the token being fed: ceil((prompt + tokens so
    far) / block_size)."""
    cfg, params = model
    block = 4
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=32,
                                   block_size=block)
    assert eng.stats()["kv_blocks_read_total"] == 0
    budgets = {3: 6, 7: 2}                    # prompt length: new tokens
    for n, new in budgets.items():
        eng.submit(list(range(1, n + 1)), max_new_tokens=new)
    eng.run()
    # a request's last token is picked, never fed: new - 1 steps, the
    # i-th of them with prompt + i tokens in the strip
    want = sum(-(-(n + i) // block)
               for n, new in budgets.items() for i in range(1, new))
    st = eng.stats()
    assert st["kv_blocks_read_total"] == want
    assert st["decode_steps"] == max(budgets.values()) - 1


def test_models_import_one_way_trunk_paging_engine():
    """``decode`` (the trunk) <- ``paging`` (the pool) <- ``generate``
    (the engine): read from the sources, nothing executed. The two
    lower modules name ``models.generate`` nowhere, and the engine
    takes ``paging`` at the top of its file, never inside a function
    (one of those ran every decode step)."""
    import ast
    import pathlib

    import kubeflow_rm_tpu.models as models

    def imported(node):
        if isinstance(node, ast.Import):
            return [a.name for a in node.names]
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            return [mod] + [f"{mod}.{a.name}" for a in node.names]
        return []

    def tree(name):
        path = pathlib.Path(models.__file__).with_name(name)
        return ast.parse(path.read_text())

    for lower in ("decode.py", "paging.py"):
        names = [n for node in ast.walk(tree(lower))
                 for n in imported(node)]
        assert names and not [n for n in names
                              if n.rsplit(".", 1)[-1] == "generate"
                              or ".generate." in n], lower
    engine = tree("generate.py")
    lazy = [n for fn in ast.walk(engine)
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
            for node in ast.walk(fn) for n in imported(node)
            if "paging" in n]
    assert lazy == []
    top = [n for node in engine.body for n in imported(node)]
    assert "kubeflow_rm_tpu.models.paging" in top
    assert "kubeflow_rm_tpu.models.decode" in top
