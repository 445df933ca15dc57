"""Cross-pool prefix-chain transfer + the disaggregated serving fleet.

The r17 tentpole contract, bottom to top:

- ``export_chain`` → ``import_chain`` into a FRESH pool decodes
  bit-identically to solo ``generate_fused`` (the chained hashes name
  content, so a chain is replica-agnostic), refcounts balance, and a
  corrupted chunk is refused without touching pool state.
- ``prefill_chain`` / ``install_chain`` split prefill from decode: the
  decode engine seats a foreign chain and starts decoding from the
  carried logits without running prefill at all.
- ``generate_speculative_fused`` rides ``submit(speculative=True)`` as
  a batch/best_effort SLO-class option and matches greedy decode
  exactly.
- ``GlobalBlockStore`` serves chains fleet-wide by hash (publish /
  truncated lookup / promote-on-evict / LRU under a byte budget), and
  a disaggregated ``ServingFleet`` survives prefill- and decode-
  replica death with sample-exact outputs — the prefix hit ratio
  survives because promoted chains outlive the pool that built them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.controlplane.serving_fleet import (
    GlobalBlockStore,
    ServingFleet,
    chain_from_bytes,
    chain_to_bytes,
)
from kubeflow_rm_tpu.controlplane.webapps.serving import (
    ServingGateway,
    TenantPolicy,
)
from kubeflow_rm_tpu.models import LlamaConfig, init_params
from kubeflow_rm_tpu.models.generate import (
    ContinuousBatchingEngine,
    generate_fused,
    generate_speculative_fused,
)
from kubeflow_rm_tpu.models.paging import (
    export_chain,
    import_chain,
    prefix_keys,
    verify_chain,
)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _engine(params, cfg, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("slot_len", 64)
    kw.setdefault("block_size", 8)
    return ContinuousBatchingEngine(params, cfg, **kw)


def _drain(eng, req):
    while not req.done:
        eng.step()
    return req.tokens


def _solo(params, cfg, prompt, n):
    out = generate_fused(params, cfg, jnp.asarray([prompt], jnp.int32),
                         max_new_tokens=n, max_len=64)
    return [int(t) for t in jax.device_get(out)[0][len(prompt):]]


PROMPT = [7, 3, 9, 1, 4, 4, 2, 8, 5, 6, 1, 2, 9, 9, 3, 1, 0, 2, 4, 6,
          11, 12, 13]


# -- export/import across pools ----------------------------------------

def test_chain_adopts_into_fresh_pool_bit_identically(model):
    """The headline: prefill on engine A, export, import into engine
    B's untouched pool — B's decode of the same prompt is bit-equal to
    solo generate_fused, and B never ran that prefill."""
    cfg, params = model
    a, b = _engine(params, cfg), _engine(params, cfg)
    ra = a.submit(PROMPT, max_new_tokens=8)
    _drain(a, ra)
    chain = export_chain(a.cache, a.pool, PROMPT)
    assert chain is not None and chain["covered"] == len(PROMPT)

    free_before = b.pool.available()
    got = import_chain(b.cache, b.pool, chain)
    assert got is not None
    b.cache, blocks = got
    assert len(blocks) == len(chain["keys"])
    b.pool.decref(blocks)  # hand to the LRU as retained prefix cache
    # refcounts balance: every imported block is retained at ref 0
    assert all(b.pool.ref_of(blk) == 0 for blk in blocks)

    rb = b.submit(PROMPT, max_new_tokens=8)
    _drain(b, rb)
    assert rb.tokens == ra.tokens == _solo(params, cfg, PROMPT, 8)
    # B prefix-cache-hit the whole imported chain
    assert b.prefix_hit_tokens >= len(PROMPT) - 1
    # retiring the request returns the pool to balance (no leaks)
    assert b.pool.available() == free_before


def test_export_is_deterministic_and_sanitized(model):
    """Identical prompts export identical bytes even when the source
    caches decoded different continuations into the tail columns."""
    cfg, params = model
    a, b = _engine(params, cfg), _engine(params, cfg)
    _drain(a, a.submit(PROMPT, max_new_tokens=8))
    _drain(b, b.submit(PROMPT, max_new_tokens=2))  # different tail use
    ca = export_chain(a.cache, a.pool, PROMPT)
    cb = export_chain(b.cache, b.pool, PROMPT)
    assert ca["sums"] == cb["sums"]
    assert ca["keys"] == cb["keys"]
    np.testing.assert_array_equal(np.asarray(ca["chunks_k"]),
                                  np.asarray(cb["chunks_k"]))


def test_corrupted_chunk_is_refused_without_pool_damage(model):
    cfg, params = model
    a, b = _engine(params, cfg), _engine(params, cfg)
    _drain(a, a.submit(PROMPT, max_new_tokens=4))
    chain = export_chain(a.cache, a.pool, PROMPT)
    chain["chunks_k"][:, 0, 0] += 1  # flip bytes in chunk 0
    free = b.pool.available()
    with pytest.raises(ValueError, match="chunk 0 checksum"):
        import_chain(b.cache, b.pool, chain)
    assert b.pool.available() == free  # refusal touched nothing
    # tokens<->keys mismatch is also refused
    good = export_chain(a.cache, a.pool, PROMPT)
    good["tokens"] = list(PROMPT[:-1]) + [99]
    with pytest.raises(ValueError, match="chained hashes"):
        verify_chain(good)


def test_import_oom_is_clean_none(model):
    cfg, params = model
    a = _engine(params, cfg)
    _drain(a, a.submit(PROMPT, max_new_tokens=4))
    chain = export_chain(a.cache, a.pool, PROMPT)
    tiny = _engine(params, cfg, slots=1, num_blocks=4)
    assert import_chain(tiny.cache, tiny.pool, chain) is None


# -- prefill/decode split on one engine pair ---------------------------

def test_prefill_chain_install_chain_skips_decode_side_prefill(model):
    cfg, params = model
    pf, dc = _engine(params, cfg), _engine(params, cfg)
    chain = pf.prefill_chain(PROMPT)
    assert chain is not None and chain["last_logits"] is not None
    assert pf.prefills == 1 and pf.chains_exported == 1

    req = dc.install_chain(chain, max_new_tokens=8)
    _drain(dc, req)
    assert req.tokens == _solo(params, cfg, PROMPT, 8)
    assert dc.prefills == 0           # decode side never prefilled
    assert dc.chain_installs == 1
    assert dc.prefix_hit_tokens == len(PROMPT)


def test_adopt_chain_counts_and_idempotence(model):
    cfg, params = model
    pf, dc = _engine(params, cfg), _engine(params, cfg)
    chain = pf.prefill_chain(PROMPT)
    assert dc.adopt_chain(chain) == len(chain["keys"])
    assert dc.adopt_chain(chain) == 0     # already fully local
    assert dc.chains_adopted == 1
    assert dc.chain_coverage(PROMPT) == len(PROMPT)


# -- speculative decode as an SLO-class option -------------------------

def test_speculative_submit_matches_greedy_exactly(model):
    cfg, params = model
    eng = _engine(params, cfg)
    with pytest.raises(ValueError, match="batch/best_effort"):
        eng.submit(PROMPT, max_new_tokens=8, speculative=True,
                   slo_class="interactive")
    req = eng.submit(PROMPT, max_new_tokens=8, speculative=True,
                     slo_class="best_effort")
    _drain(eng, req)
    assert req.tokens == _solo(params, cfg, PROMPT, 8)
    assert eng.speculative_requests == 1
    # verification rounds are bounded: one extra call past the budget at
    # worst (when no drafts accept), fewer when drafts land
    assert 1 <= eng.speculative_model_calls <= 9


def test_speculative_fused_stats_accept_drafts(model):
    # a periodic prompt gives the n-gram drafter something to latch on to,
    # so accepted drafts must show up as saved model calls
    cfg, params = model
    loop = [5, 9, 2] * 8
    stats = {}
    out = generate_speculative_fused(
        params, cfg, jnp.asarray([loop], jnp.int32),
        max_new_tokens=24, stats=stats)
    got = [int(t) for t in jax.device_get(out)[0][len(loop):]]
    assert got == _solo(params, cfg, loop, 24)
    assert stats["tokens_out"] == 24
    assert stats["model_calls"] < 24  # drafts accepted, calls saved


# -- the global block store --------------------------------------------

def _publish_prompt(store, eng, prompt):
    chain = eng.prefill_chain(prompt)
    store.publish(chain)
    return chain


def test_store_lookup_full_and_truncated(model):
    cfg, params = model
    eng = _engine(params, cfg)
    store = GlobalBlockStore()
    _publish_prompt(store, eng, PROMPT)
    # exact prompt: full chain, logits ride along -> install path
    hit = store.lookup(prefix_keys(PROMPT, 8))
    assert hit["tokens"] == PROMPT and "last_logits" in hit
    # shared prefix, different tail: truncated chain, NO logits
    other = PROMPT[:16] + [21, 22, 23]
    part = store.lookup(prefix_keys(other, 8))
    assert part is not None and part["covered"] == 16
    assert "last_logits" not in part
    verify_chain(part)                # truncation stays verifiable
    # disjoint prompt: miss
    assert store.lookup(prefix_keys([31, 32, 33, 34], 8)) is None
    st = store.stats()
    assert st["hits"] == 2 and st["misses"] == 1


def test_store_supersedes_prefixes_and_respects_byte_budget(model):
    cfg, params = model
    eng = _engine(params, cfg)
    short = _publish_prompt(GlobalBlockStore(), eng, PROMPT[:8])
    store = GlobalBlockStore(max_bytes=4 * short["nbytes"])
    store.publish(short)
    longer = export_chain(eng.cache, eng.pool, PROMPT[:16]) \
        or eng.prefill_chain(PROMPT[:16])
    store.publish(longer)
    st = store.stats()
    assert st["superseded"] == 1 and st["chains"] == 1
    # unrelated chains LRU out under the byte budget
    for i in range(4):
        _publish_prompt(store, eng, [40 + i] * 16)
    st = store.stats()
    assert st["evicted"] > 0
    assert st["bytes"] <= store.max_bytes


def test_store_wire_roundtrip(model):
    cfg, params = model
    eng = _engine(params, cfg)
    store = GlobalBlockStore()
    _publish_prompt(store, eng, PROMPT)
    entry = store.lookup(prefix_keys(PROMPT, 8))
    back = chain_from_bytes(chain_to_bytes(entry))
    verify_chain(back)
    assert back["tokens"] == entry["tokens"]
    np.testing.assert_array_equal(back["chunks_k"],
                                  np.asarray(entry["chunks_k"]))
    np.testing.assert_array_equal(back["last_logits"],
                                  np.asarray(entry["last_logits"]))
    with pytest.raises(ValueError):
        chain_from_bytes(b"\x00\x00\x00\x05xxxxx")


# -- the disaggregated fleet -------------------------------------------

_POL = TenantPolicy(qps=1e9, burst=10**6, tokens_per_s=1e9,
                    token_burst=10**7, slo_p95_ms=1e9)


def _fleet(params, cfg, *, blocks=None):
    blocks = blocks or {}
    gws = {n: ServingGateway(
        _engine(params, cfg, slots=2, num_blocks=blocks.get(n)),
        default_policy=_POL)
        for n in ("pf0", "dc0", "dc1")}
    return ServingFleet(gws, roles={"pf0": "prefill", "dc0": "decode",
                                    "dc1": "decode"}), gws


def test_disagg_fleet_routes_through_prefill_tier(model):
    cfg, params = model
    fleet, gws = _fleet(params, cfg)
    try:
        toks, info = fleet.submit_and_wait("t", PROMPT,
                                           max_new_tokens=8)
        assert toks == _solo(params, cfg, PROMPT, 8)
        assert info["replicas"][0].startswith("dc")
        assert fleet.handoffs == 1
        assert gws["pf0"].engine.chains_exported == 1
        # the decode replica installed the chain instead of prefilling
        eng = gws[info["replicas"][0]].engine
        assert eng.chain_installs == 1 and eng.prefills == 0
        assert fleet.store.stats()["published"] >= 1
        snap = fleet.snapshot()
        assert snap["roles"]["pf0"] == "prefill"
        assert snap["store"]["chains"] >= 1
    finally:
        fleet.close()


def test_disagg_fleet_validates_roles(model):
    cfg, params = model
    gw = ServingGateway(_engine(params, cfg), default_policy=_POL)
    try:
        with pytest.raises(ValueError, match="decode replica"):
            ServingFleet({"a": gw}, roles={"a": "prefill"})
        with pytest.raises(ValueError, match="unknown roles"):
            ServingFleet({"a": gw}, roles={"a": "router"})
        with pytest.raises(ValueError, match="every replica"):
            ServingFleet({"a": gw}, roles={})
    finally:
        gw.close()


def test_disagg_survives_prefill_replica_death(model):
    """Kill the whole prefill tier: requests fall back to decode-local
    prefill, outputs stay sample-exact."""
    cfg, params = model
    fleet, _gws = _fleet(params, cfg)
    try:
        fleet.kill("pf0")
        toks, info = fleet.submit_and_wait("t", PROMPT,
                                           max_new_tokens=8)
        assert toks == _solo(params, cfg, PROMPT, 8)
        assert fleet.handoffs == 0
    finally:
        fleet.close()


def test_disagg_prefix_survives_decode_replica_death(model):
    """The r13 failure this PR exists for: kill the decode replica
    whose pool holds the hot prefix. With the global store the
    surviving replica adopts the chain by hash and the prefix hit
    ratio survives; outputs stay bit-exact throughout."""
    cfg, params = model
    # tiny dc0 pool so its chain churns into the store via promotion
    fleet, gws = _fleet(params, cfg, blocks={"dc0": 34})
    try:
        fleet.kill("pf0")   # force decode-local prefill: the prefix
        # now exists ONLY in dc0's pool (routing favors the shallower
        # tiny replica equally; pin the first request's home)
        toks, info = fleet.submit_and_wait("t", PROMPT,
                                           max_new_tokens=8)
        ref = _solo(params, cfg, PROMPT, 8)
        assert toks == ref
        holder = info["replicas"][0]
        # churn the holder's pool with unrelated prompts -> promotion
        for i in range(12):
            fleet.submit_and_wait("t", [30 + i, 31 + i, 32 + i] * 8,
                                  max_new_tokens=4)
        assert fleet.store.stats()["promoted"] > 0
        fleet.kill(holder)
        survivor = next(n for n, r in fleet.roles.items()
                        if r == "decode" and n != holder)
        eng = gws[survivor].engine
        hit0, tok0 = eng.prefix_hit_tokens, eng.prompt_tokens
        toks2, info2 = fleet.submit_and_wait("t", PROMPT,
                                             max_new_tokens=8)
        assert toks2 == ref                      # sample-exact
        assert info2["replicas"] == [survivor]
        # the probe's prompt tokens were largely absorbed by chains
        # recovered from the store — the hit ratio survived the death
        hit = (eng.prefix_hit_tokens - hit0) / (eng.prompt_tokens
                                                - tok0)
        assert hit > 0.5, hit
    finally:
        fleet.close()


def test_disagg_speculative_is_exact_through_the_fleet(model):
    cfg, params = model
    fleet, _gws = _fleet(params, cfg)
    try:
        toks, _info = fleet.submit_and_wait(
            "t", PROMPT, max_new_tokens=8, slo_class="best_effort",
            speculative=True)
        assert toks == _solo(params, cfg, PROMPT, 8)
    finally:
        fleet.close()
