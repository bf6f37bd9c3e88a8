"""Model correctness: paged decode must reproduce dense prefill exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.kv import (
    BlockAllocator,
    PagedCacheConfig,
    init_cache,
    prefill_to_pages,
    write_pages,
)
from infinistore_tpu.models import (
    TINY,
    causal_attention,
    decode_forward,
    init_params,
    prefill_forward,
    scaled,
    train_step_fn,
)


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = scaled(TINY, dtype=jnp.float32)  # fp32 on CPU for exact comparisons
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_causal_attention_matches_naive():
    B, S, H, D = 2, 8, 4, 16
    key = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(kk, (B, S, H, D), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    out = causal_attention(q, k, v)
    # naive per-position reference
    for b in range(B):
        for i in range(S):
            logits = np.einsum("hd,khd->hk", q[b, i], k[b, : i + 1]) / np.sqrt(D)
            p = jax.nn.softmax(jnp.asarray(logits), axis=-1)
            ref = np.einsum("hk,khd->hd", p, v[b, : i + 1])
            np.testing.assert_allclose(out[b, i], ref, rtol=2e-5, atol=2e-5)


def test_prefill_shapes(tiny_setup):
    cfg, params = tiny_setup
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, cfg.vocab_size)
    logits, kv = jax.jit(lambda p, t: prefill_forward(p, cfg, t))(params, tokens)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert kv.shape == (cfg.n_layers, 2, 2, 32, cfg.n_kv_heads, cfg.head_dim)


def test_paged_decode_matches_prefill(tiny_setup):
    """Feed a sequence through prefill, then decode the last tokens one by one
    via the paged cache -- logits must match the dense forward."""
    cfg, params = tiny_setup
    T = 4  # block_tokens
    S_prefill, S_total = 8, 12
    B = 1
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S_total), 0, cfg.vocab_size)

    # dense reference over the full sequence
    ref_logits, _ = prefill_forward(params, cfg, tokens)

    # paged: prefill first 8 tokens, page the kv, then decode tokens 8..11
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        n_blocks=16,
        block_tokens=T,
        dtype=cfg.dtype,
    )
    cache = init_cache(pc)
    alloc = BlockAllocator(pc.n_blocks)
    _, kv = prefill_forward(params, cfg, tokens[:, :S_prefill])
    n_pages = S_prefill // T
    pages = prefill_to_pages(kv[:, :, 0], n_pages, T)  # batch 0
    block_ids = alloc.alloc(n_pages + 1)  # one extra page for decode growth
    cache = write_pages(cache, jnp.asarray(block_ids[:n_pages]), pages)

    table = np.zeros((B, 4), dtype=np.int32)
    table[0, : n_pages + 1] = block_ids
    block_table = jnp.asarray(table)

    for pos in range(S_prefill, S_total):
        seq_lens = jnp.asarray([pos + 1], dtype=jnp.int32)
        slot_block = jnp.asarray([block_ids[pos // T]], dtype=jnp.int32)
        slot = jnp.asarray([pos % T], dtype=jnp.int32)
        logits, cache = decode_forward(
            params,
            cfg,
            tokens[:, pos],
            jnp.asarray([pos]),
            cache,
            block_table,
            seq_lens,
            slot_block,
            slot,
        )
        np.testing.assert_allclose(
            np.asarray(logits[0]),
            np.asarray(ref_logits[0, pos]),
            rtol=2e-4,
            atol=2e-4,
        )


def test_windowed_paged_decode_matches_prefill():
    """Sliding-window config: the paged decode mask must agree with the
    prefill mask.  Window (5) < prefilled length (8) so decode positions
    genuinely drop early keys, and a full-causal decode would diverge."""
    cfg = scaled(TINY, dtype=jnp.float32, sliding_window=5)
    params = init_params(cfg, jax.random.PRNGKey(7))
    T = 4
    S_prefill, S_total = 8, 12
    tokens = jax.random.randint(jax.random.PRNGKey(8), (1, S_total), 0, cfg.vocab_size)

    ref_logits, _ = prefill_forward(params, cfg, tokens)
    full_cfg = scaled(cfg, sliding_window=None)
    full_logits, _ = prefill_forward(params, full_cfg, tokens)
    assert not np.allclose(  # the window must actually bite
        np.asarray(ref_logits[0, -1]), np.asarray(full_logits[0, -1]),
        rtol=2e-4, atol=2e-4,
    )

    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=16, block_tokens=T, dtype=cfg.dtype,
    )
    cache = init_cache(pc)
    alloc = BlockAllocator(pc.n_blocks)
    _, kv = prefill_forward(params, cfg, tokens[:, :S_prefill])
    n_pages = S_prefill // T
    block_ids = alloc.alloc(n_pages + 1)
    cache = write_pages(
        cache, jnp.asarray(block_ids[:n_pages]),
        prefill_to_pages(kv[:, :, 0], n_pages, T),
    )
    table = np.zeros((1, 4), dtype=np.int32)
    table[0, : n_pages + 1] = block_ids
    for pos in range(S_prefill, S_total):
        logits, cache = decode_forward(
            params, cfg, tokens[:, pos], jnp.asarray([pos]), cache,
            jnp.asarray(table), jnp.asarray([pos + 1], dtype=jnp.int32),
            jnp.asarray([block_ids[pos // T]], dtype=jnp.int32),
            jnp.asarray([pos % T], dtype=jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(ref_logits[0, pos]),
            rtol=2e-4, atol=2e-4,
        )


def test_train_step_reduces_loss(tiny_setup):
    cfg, params = tiny_setup
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 16), 0, cfg.vocab_size)
    step = jax.jit(train_step_fn(cfg, lr=1e-2))
    _, loss0 = step(params, tokens)
    p, _ = step(params, tokens)
    for _ in range(5):
        p, loss = step(p, tokens)
    assert float(loss) < float(loss0)


# ---- seeded weights and the --model <file>.json resolver ----


def _init_params_loop(cfg, key):
    """The per-layer loop init_params replaced (it held every layer twice:
    a list of per-layer dicts, then their stack), kept as the reference."""
    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(cfg.dtype)

    keys = jax.random.split(key, cfg.n_layers + 2)
    hd = cfg.head_dim
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[li], 10)
        layer = {
            "wq": dense(k[0], (cfg.dim, cfg.n_heads * hd), cfg.dim),
            "wk": dense(k[1], (cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
            "wv": dense(k[2], (cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
            "wo": dense(k[3], (cfg.n_heads * hd, cfg.dim), cfg.n_heads * hd),
            "w_gate": dense(k[4], (cfg.dim, cfg.ffn_dim), cfg.dim),
            "w_up": dense(k[5], (cfg.dim, cfg.ffn_dim), cfg.dim),
            "w_down": dense(k[6], (cfg.ffn_dim, cfg.dim), cfg.ffn_dim),
            "ln_attn": jnp.ones((cfg.dim,), cfg.dtype),
            "ln_mlp": jnp.ones((cfg.dim,), cfg.dtype),
        }
        if cfg.attn_bias:
            layer["bq"] = dense(k[7], (cfg.n_heads * hd,), cfg.dim)
            layer["bk"] = dense(k[8], (cfg.n_kv_heads * hd,), cfg.dim)
            layer["bv"] = dense(k[9], (cfg.n_kv_heads * hd,), cfg.dim)
        if cfg.qk_norm:
            layer["q_norm"] = jnp.ones((hd,), cfg.dtype)
            layer["k_norm"] = jnp.ones((hd,), cfg.dtype)
        layers.append(layer)
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, cfg.dim), cfg.dim),
        "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
        "ln_out": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": dense(keys[-1], (cfg.dim, cfg.vocab_size), cfg.dim),
    }


def test_init_params_matches_per_layer_loop():
    """The stacked, jitted init draws the same weights as the loop.  Under
    jit the f32 divide and the bf16 cast fuse, so a value may land on the
    neighbouring bf16 (8 significant bits: one part in 2^7 at most)."""
    cfg = scaled(TINY, attn_bias=True, qk_norm=True, head_dim_override=64)
    got = init_params(cfg, jax.random.PRNGKey(3))
    want = _init_params_loop(cfg, jax.random.PRNGKey(3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=2.0 ** -7, atol=0,
        )


def test_model_config_file_resolver(tmp_path):
    import json
    import os

    from infinistore_tpu.models import QWEN3_8B, load_config_file

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model_id, cfg, seed = load_config_file(
        os.path.join(repo, "configs", "qwen3_8b_l12.json"))
    # every width as published; only the depth is cut
    assert cfg == scaled(QWEN3_8B, n_layers=12) and seed == 0
    assert model_id == "qwen3_8b-l12-seed0"
    _, tiny, _ = load_config_file(os.path.join(repo, "configs", "tiny.json"))
    assert tiny == TINY

    def load(spec):
        path = tmp_path / "m.json"
        path.write_text(json.dumps(spec))
        return load_config_file(str(path))

    ok = {"preset": "QWEN3_8B", "reduced": {"n_layers": 2}, "seed": 7}
    assert load(ok)[0] == "qwen3_8b-l2-seed7"
    for bad in (
        {**ok, "reduced": {"n_layers": 2, "dim": 1024}},   # a width cut
        {**ok, "published": {"ffn_dim": 4096}},            # a width override
        {**ok, "reduced": {"n_layers": 37}},               # deeper than published
        {**ok, "preset": "MIXTRAL_8X7B"},                  # not a dense preset
        {**ok, "preset": "init_params"},
        {**ok, "seed": -1},
    ):
        with pytest.raises(ValueError):
            load(bad)


def test_compile_cache_dir_rule():
    """Placed from outside when JAX_COMPILATION_CACHE_DIR is set, a fixed
    path in the checkout otherwise, nothing while the platform is pinned
    to the CPU (this suite)."""
    import os

    from infinistore_tpu import jaxcfg

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jaxcfg.default_cache_dir({}) == os.path.join(repo, ".jax_cache")
    assert jaxcfg.default_cache_dir({"JAX_PLATFORMS": "tpu"}) == \
        os.path.join(repo, ".jax_cache")
    assert jaxcfg.default_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}) is None
    assert jaxcfg.default_cache_dir({"JAX_PLATFORMS": "cpu"}) is None
    # conftest pins cpu, so importing the package set no directory here
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert jax.config.jax_compilation_cache_dir is None
