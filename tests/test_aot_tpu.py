"""What XLA:TPU makes of the decode step, asked of the TPU compiler without
a chip: a compile-only ``v5e:2x2`` topology, as ``benchmarks/aot_check.py``
uses it.  Nothing runs and nothing is timed.

The CPU tests cannot see this.  On the chip a slice of the cache that feeds
a gather is materialised: when the attention was handed ``cache[layer]``
the optimized program copied that layer's slab (and K's and V's halves of
it) in every layer of every step, two thirds of the decode scan (PERF.md,
PR 27).  Every compile-only test lives in this file, and the topology is
described inside a fixture, so only the worker that runs the file loads the
TPU's library; where the topology cannot be had the tests skip."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from infinistore_tpu import models
from infinistore_tpu.kv import PagedCacheConfig, init_cache
from infinistore_tpu.parallel.sharding import (
    llama_inference_specs,
    make_tp_decode,
    make_tp_prefill,
    shardings_for,
)

T, N_BLOCKS, WIDTH = 16, 384, 64

# name, result type, opcode of every instruction of an optimized module
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?(\w+\[[\d,]*\])\S* ([\w\-]+)\(", re.M)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _family(preset):
    cfg = models.scaled(getattr(models, preset), n_layers=2)
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=N_BLOCKS, block_tokens=T,
        dtype=cfg.dtype,
    )
    return cfg, pc


def _shaped(tree, sharding):
    """Abstract arrays of ``tree``'s shapes, placed by ``sharding`` (one
    sharding, or a pytree of them)."""
    if not isinstance(sharding, dict):
        sharding = jax.tree.map(lambda _: sharding, tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding,
    )


def _slab_copies(text, heads, pc):
    """Instructions of the optimized program whose result is one layer's
    slab of the cache, or K's or V's half of it, and that are neither a
    parameter nor a bitcast."""
    dims = (heads, pc.n_blocks, pc.block_tokens, pc.head_dim)
    slabs = {"bf16[%s]" % ",".join(map(str, d)) for d in (dims, (2,) + dims)}
    found = _INSTRUCTION.findall(text)
    assert len(found) > 100, "the optimized program did not parse"
    return [
        f"{name} = {shape} {op}" for name, shape, op in found
        if shape in slabs and op not in ("parameter", "bitcast")
    ]


@functools.cache
def _decode_scan_on_tpu(preset, batch, device, width=WIDTH):
    """``decode_forward`` in a short scan as the engine runs it, at the
    family's published widths (two layers, a small cache), compiled for one
    described chip; compiled once for the tests that read it.
    -> (cfg, pc, the parameters' and the cache's abstract arrays, compiled)"""
    cfg, pc = _family(preset)
    chip = SingleDeviceSharding(device)
    params = _shaped(jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))), chip)
    cache = _shaped(jax.eval_shape(lambda: init_cache(pc)), chip)

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jnp.take_along_axis(table, (p // T)[:, None], axis=1)[:, 0]
            logits, cache = models.decode_forward(
                params, cfg, tok, p, cache, table, p + 1, blocks, p % T)
            return (logits, cache), tok

        (logits, cache), toks = jax.lax.scan(
            step, (logits, cache), jnp.arange(3))
        return toks, logits, cache

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = jax.jit(decode_scan, donate_argnums=(3,)).lower(
        params, sds((batch, cfg.vocab_size), cfg.dtype),
        sds((batch,), jnp.int32), cache, sds((batch, width), jnp.int32),
    ).compile()
    return cfg, pc, params, cache, compiled


def _bf16_results_of(text, elements, weights):
    """Instructions of the optimized program whose result is a bf16 array of
    ``elements`` elements, in any order of dimensions, that are neither a
    parameter, a bitcast nor of a weight's own shape."""
    return [
        f"{name} = {shape} {op}" for name, shape, op in _INSTRUCTION.findall(text)
        if shape.startswith("bf16[") and shape != "bf16[]"
        and op not in ("parameter", "bitcast") and shape not in weights
        and int(np.prod([int(d) for d in shape[5:-1].split(",")])) == elements
    ]


def _weight_shapes(params):
    return {"bf16[%s]" % ",".join(map(str, w.shape))
            for w in jax.tree.leaves(params)}


def _kernel_calls(text, kernel="paged_decode_attention"):
    """The optimized program's calls of a kernel, by the name it gives them:
    the decode attention's (models/paged_decode_kernel.py) or the prefill
    chunk's (``chunk_attention``, models/chunk_attention_kernel.py)."""
    return [name for name, _, op in _INSTRUCTION.findall(text)
            if op == "custom-call" and kernel in name]


dense_cells = pytest.mark.parametrize(
    "preset,batch",
    [(p, b) for p in ("QWEN3_8B", "QWEN25_7B") for b in (2, 8)])


@dense_cells
def test_decode_scan_on_tpu_copies_no_slab_of_the_cache(preset, batch, v5e):
    """The benchmark's two families at their published widths (two layers,
    a small cache), ``decode_forward`` in a short scan as the engine runs
    it: nothing of a slab's shape is computed, and the cache that comes
    out is the donated one."""
    _, pc, _, cache, compiled = _decode_scan_on_tpu(preset, batch, v5e[0])
    copies = _slab_copies(compiled.as_text(), pc.n_kv_heads, pc)
    assert not copies, copies
    cache_bytes = int(np.prod(cache.shape)) * cache.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes, (
        "the cache output no longer aliases the donated input", mem)


@dense_cells
def test_decode_scan_on_tpu_repeats_no_keys_or_values(preset, batch, v5e):
    """The same programs hold no bf16 result of B x S x H x D elements
    (S the table's tokens, H the QUERY heads), in any order of dimensions,
    that is neither a parameter nor a bitcast: the gathered keys and values
    are contracted against the grouped query as they are, [B, S, H_kv, D].
    ``repeat_kv`` before the einsums was written out by XLA:TPU and read
    back, G times the gathered bytes per K and per V per layer (PERF.md,
    PR 31).  The instructions inside fusions are read too; a result of a
    weight's own shape is a weight (Qwen3's two layers of ``wq`` have as
    many elements as 8 rows x 1,024 tokens x 32 heads x 128)."""
    cfg, pc, params, _, compiled = _decode_scan_on_tpu(preset, batch, v5e[0])
    assert cfg.n_heads > cfg.n_kv_heads
    weights = {"bf16[%s]" % ",".join(map(str, w.shape))
               for w in jax.tree.leaves(params)}
    repeated = batch * WIDTH * T * cfg.n_heads * cfg.head_dim
    gathered = batch * WIDTH * T * cfg.n_kv_heads * cfg.head_dim
    found = [
        (name, shape, op,
         int(np.prod([int(d) for d in shape[5:-1].split(",")])))
        for name, shape, op in _INSTRUCTION.findall(compiled.as_text())
        if shape.startswith("bf16[") and shape != "bf16[]"
        and op not in ("parameter", "bitcast") and shape not in weights
    ]
    assert len(found) > 100, "the optimized program did not parse"
    # the reader sees this program's attention: since PR 36 the kernel that
    # copies live pages, a layer each; not even the gathered table is there
    assert len(_kernel_calls(compiled.as_text())) == cfg.n_layers
    copies = [f"{name} = {shape} {op}" for name, shape, op, n in found
              if n in (repeated, gathered)]
    assert not copies, copies


@pytest.mark.parametrize("preset,batch,width", [
    ("QWEN3_8B", 2, 256), ("QWEN3_8B", 8, 256), ("QWEN25_7B", 32, 256)])
def test_decode_scan_on_tpu_at_the_cells_tables_gathers_no_table(
        preset, batch, width, v5e):
    """The dense cells' widest programs (``doc-reask``: up to 8 rows x 256
    pages; ``batch-summarize``: 32 x 256): the optimized program holds no
    bf16 result with the elements of the gathered table, [B * pages, H_kv,
    T, D] or its layout copy [B, pages, T, H_kv, D] (63% of the scan at 32
    rows: PERF.md, PR 33), nor a slab; a layer's attention is one call of the
    kernel, which is handed the whole cache where it lies; the cache that
    comes out is the donated one; and the scan's temporaries are the logits'
    and the two layers' re-laid weights, not the table's (the compiler's
    count, PR 35's tree -> PR 36's: 111 -> 111 MB at 2 x 256, 192 -> 104 at
    8 x 256, 361 -> 61 at 32 x 256 and 668 -> 61 at 32 x 512)."""
    cfg, pc, params, cache, compiled = _decode_scan_on_tpu(
        preset, batch, v5e[0], width)
    text = compiled.as_text()
    assert len(_INSTRUCTION.findall(text)) > 100, "the optimized program did not parse"
    tables = _bf16_results_of(
        text, batch * width * T * cfg.n_kv_heads * cfg.head_dim,
        _weight_shapes(params))
    assert not tables, tables
    assert not _slab_copies(text, pc.n_kv_heads, pc)
    assert len(_kernel_calls(text)) == cfg.n_layers
    mem = compiled.memory_analysis()
    cache_bytes = int(np.prod(cache.shape)) * cache.dtype.itemsize
    assert mem.alias_size_in_bytes >= cache_bytes, mem
    assert mem.temp_size_in_bytes < 128 << 20, mem


@pytest.mark.parametrize("preset", ["QWEN25_7B", "QWEN3_8B"])
def test_prefill_chunk_on_tpu_writes_no_scores_and_repeats_no_keys(preset, v5e):
    """The dense cells' largest prefill program (a 512-token chunk over the
    4,096-row prefix bucket, twelve layers at the published widths, the head
    on one row so that the last layer's attention is live): every layer's
    attention is one call of the chunk kernel; no instruction, inside a fusion
    or out, has a result of ``[H, 512, Sk]`` scores in float32 or bf16 (the
    XLA form wrote ``bf16[28,512,4608]`` and ``f32[28,512,4608]`` a layer: 792
    MB of traffic; PERF.md, PR 48) nor of the ``repeat_kv`` broadcast's
    ``Sk x H x D`` elements; the temporaries are the layers' own, not the
    scores' (811 MB with the XLA form, 355 with the kernel at 28 heads).  The
    compile's seconds are printed: the kernel is lowered once a program."""
    import time

    cfg = models.scaled(getattr(models, preset), n_layers=12)
    chip = SingleDeviceSharding(v5e[0])
    params = _shaped(jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))), chip)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    chunk, bucket = 512, 4096
    t0 = time.perf_counter()
    compiled = jax.jit(
        lambda p, t, kv, n, row: models.prefill_forward(
            p, cfg, t, prefix_kv=kv, prefix_len=n, head="row", head_row=row)
    ).lower(
        params, sds((1, chunk), jnp.int32),
        sds((cfg.n_layers, 2, 1, bucket, cfg.n_kv_heads, cfg.head_dim),
            cfg.dtype),
        sds((), jnp.int32), sds((1,), jnp.int32)).compile()
    print(f"\n{preset}: the chunk's program over a {bucket}-row bucket lowered "
          f"and compiled in {time.perf_counter() - t0:.1f} s")
    text = compiled.as_text()
    found = _INSTRUCTION.findall(text)
    assert len(found) > 100, "the optimized program did not parse"
    assert len(_kernel_calls(text, "chunk_attention")) == cfg.n_layers
    # and the model's own count, which the engine's counter reads
    # (``prefill.attn_kernel_chunks``), says what the compiler was given
    assert models.prefill_forward.kernel_layers(
        cfg, sds((1, chunk), jnp.int32),
        prefix_kv=sds((cfg.n_layers, 2, 1, bucket, cfg.n_kv_heads,
                       cfg.head_dim), cfg.dtype),
        prefix_len=sds((), jnp.int32)) == cfg.n_layers
    scores = re.compile(r"(?:f32|bf16)\[(?:1,)?%d,%d,\d+\]" % (cfg.n_heads, chunk))
    written = [f"{name} = {shape} {op}" for name, shape, op in found
               if scores.fullmatch(shape)]
    assert not written, written
    repeated = _bf16_results_of(
        text, (bucket + chunk) * cfg.n_heads * cfg.head_dim,
        _weight_shapes(params))
    assert not repeated, repeated
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


@pytest.mark.parametrize("forward,cfg_kwargs,rows,prefix,padded,layers", [
    ("prefill_forward", {}, 512, 1024, True, 4),
    ("prefill_forward", {}, 512, None, False, 4),              # a first chunk
    ("prefill_forward", {}, 1024, None, False, 4),             # two query blocks
    ("prefill_forward", {}, 256, 1024, True, 0),               # a short last chunk
    ("prefill_forward", {}, 512, 1024, False, 0),              # an exact prefix
    ("prefill_forward", {"sliding_window": 256, "window_pattern": 2}, 512,
     1024, True, 2),
    ("prefill_forward", {"sliding_window": 256}, 512, 1024, True, 0),
    ("prefill_forward", {"attn_softcap": 30.0}, 512, 1024, True, 0),
    ("prefill_forward", {"dtype": jnp.float32}, 512, 1024, True, 0),
    ("moe_prefill_forward", {}, 512, 1024, True, 4),
    ("moe_prefill_forward", {"sliding_window": 256}, 512, 1024, True, 0),
], ids=["padded-prefix", "first-chunk", "two-query-blocks", "short-chunk",
        "exact-prefix", "alternating-windows", "window", "softcap", "float32",
        "moe", "moe-window"])
def test_the_models_count_of_kernel_layers_is_the_compiled_programs(
        forward, cfg_kwargs, rows, prefix, padded, layers, v5e):
    """``prefill.attn_kernel_chunks`` rests on the model's own reading of
    its program (``prefill_forward.kernel_layers``, the engine's
    ``_chunk_attention_in_kernel``): for a described v5e that reading IS the
    number of ``chunk_attention`` calls in the compiled program, for the
    dense forward and the routed-expert one (models/moe.py), over windows,
    a soft cap, the dtype, the chunk's rows and the prefix's form."""
    base = models.TINY_MOE if forward.startswith("moe") else models.TINY
    cfg = models.scaled(base, **{"n_layers": 4, "head_dim_override": 128,
                                 "dtype": jnp.bfloat16, **cfg_kwargs})
    init = (models.init_moe_params if forward.startswith("moe")
            else models.init_params)
    fwd = getattr(models, forward)
    chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    kw = {}
    if prefix is not None:
        kw["prefix_kv"] = sds((cfg.n_layers, 2, 1, prefix, cfg.n_kv_heads,
                               cfg.head_dim), cfg.dtype)
    if padded:
        kw["prefix_len"] = sds((), jnp.int32)
    params = _shaped(jax.eval_shape(
        lambda: init(cfg, jax.random.PRNGKey(0))), chip)
    tokens = sds((1, rows), jnp.int32)
    text = jax.jit(lambda p, t, kw: fwd(p, cfg, t, **kw)).lower(
        params, tokens, kw).compile().as_text()
    assert fwd.kernel_layers(cfg, tokens, **kw) == layers
    assert len(_kernel_calls(text, "chunk_attention")) == layers


def test_a_differentiated_program_on_tpu_holds_the_xla_form_alone(v5e):
    """``loss_fn`` at a whole chunk's shapes in bf16, compiled for one
    described chip: the forward alone holds the chunk kernel, a layer each;
    its gradient holds none (a Pallas call has no derivative: under
    differentiation the kernel's branch is the XLA form, forward pass and
    backward, so a train step's scores are computed once, by the form that
    is differentiated)."""
    cfg = models.scaled(models.TINY, n_layers=2, head_dim_override=128,
                        dtype=jnp.bfloat16)
    chip = SingleDeviceSharding(v5e[0])
    params = _shaped(jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))), chip)
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=chip)
    loss = lambda p, t: models.loss_fn(p, cfg, t)
    forward = jax.jit(loss).lower(params, tokens).compile().as_text()
    assert len(_kernel_calls(forward, "chunk_attention")) == cfg.n_layers
    grad = jax.jit(jax.grad(loss)).lower(params, tokens).compile().as_text()
    assert len(_INSTRUCTION.findall(grad)) > 100, "the optimized program did not parse"
    assert not _kernel_calls(grad, "chunk_attention")


def test_tp_prefill_on_tpu_keeps_the_xla_attention_and_lowers(v5e):
    """``make_tp_prefill`` over four chips (GSPMD over the heads) at a whole
    chunk's shapes in bf16, where a single chip's program holds the chunk
    kernel: it names its mesh while the model is traced, so the attention
    keeps its XLA form and the program lowers.  The same jit WITHOUT the
    mesh named does not lower at all: the partitioner cannot split a Mosaic
    kernel by itself, which is why every jit of the model over a mesh names
    it (``make_tp_decode``, the engine's ``_traced_under``)."""
    cfg, _ = _family("QWEN3_8B")
    mesh = Mesh(np.array(v5e).reshape(1, len(v5e)), ("dp", "tp"))
    specs = shardings_for(mesh, llama_inference_specs(cfg=cfg))
    params = _shaped(
        jax.eval_shape(lambda: models.init_params(cfg, jax.random.PRNGKey(0))),
        specs)
    data = NamedSharding(mesh, P("dp", None))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=data)
    assert models.prefill_forward.kernel_layers(cfg, tokens) == cfg.n_layers
    text = make_tp_prefill(cfg, mesh).lower(params, tokens).compile().as_text()
    assert len(_INSTRUCTION.findall(text)) > 100, "the optimized program did not parse"
    assert not _kernel_calls(text, "chunk_attention")
    unnamed = jax.jit(lambda p, t: models.prefill_forward(p, cfg, t),
                      in_shardings=(specs, data))
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        unnamed.lower(params, tokens)


def test_tp_decode_on_tpu_copies_no_slab_and_gathers_no_cache(v5e):
    """``make_tp_decode`` over four chips (GSPMD over the KV-head axis):
    no chip copies its share of a slab, the cache is not all-gathered, and
    the only collectives are the all-reduces after ``wo`` and the MLP."""
    cfg, pc = _family("QWEN3_8B")
    tp, batch = len(v5e), 4
    mesh = Mesh(np.array(v5e).reshape(1, tp), ("dp", "tp"))
    repl = NamedSharding(mesh, P())
    cache_sharding = NamedSharding(mesh, P(None, None, "tp"))
    params = _shaped(
        jax.eval_shape(lambda: models.init_params(cfg, jax.random.PRNGKey(0))),
        shardings_for(mesh, llama_inference_specs(cfg=cfg)),
    )
    cache = _shaped(jax.eval_shape(lambda: init_cache(pc)), cache_sharding)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)
    compiled = make_tp_decode(cfg, mesh).lower(
        params, ints(batch), ints(batch), cache, ints(batch, WIDTH),
        ints(batch), ints(batch), ints(batch),
    ).compile()
    text = compiled.as_text()
    copies = _slab_copies(text, pc.n_kv_heads // tp, pc)
    assert not copies, copies
    collectives = [
        op for _, _, op in _INSTRUCTION.findall(text)
        if op.split("-start")[0] in (
            "all-gather", "all-reduce", "all-to-all", "collective-permute",
            "reduce-scatter")
    ]
    assert collectives and set(collectives) <= {"all-reduce", "all-reduce-start"}, (
        collectives)
    shard_bytes = int(np.prod(cache.shape)) * cache.dtype.itemsize // tp
    assert compiled.memory_analysis().alias_size_in_bytes >= shard_bytes


def test_latent_decode_scan_on_tpu_copies_no_expert_leaf_and_no_slab(v5e):
    """The latent-attention, routed-expert family at its published widths
    (the leading dense layer and one expert layer, the benchmark's 10,240
    blocks and 1,024-page tables: a cache of a few MB is moved into fast
    memory whole and tells nothing): the
    decode scan computes nothing of the shape of a layer's expert matrices
    (a slice of a leaf stacked over layers feeding the grouped product was
    copied in every step: 384 MB a leaf; the leaves are a layer's own now),
    nothing of a slab's shape, and the one-plane cache that comes out is the
    donated one."""
    cfg = models.MlaMoeConfig(n_layers=2)
    pc = PagedCacheConfig.for_model(cfg, 10240, T)
    assert (pc.planes, pc.n_kv_heads, pc.head_dim) == (1, 1, 576)
    chip = SingleDeviceSharding(v5e[0])
    params = _shaped(jax.eval_shape(
        lambda: models.init_mla_moe_params(cfg, jax.random.PRNGKey(0))), chip)
    cache = _shaped(jax.eval_shape(lambda: init_cache(pc)), chip)
    batch = 8

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jnp.take_along_axis(table, (p // T)[:, None], axis=1)[:, 0]
            logits, cache = models.mla_moe_decode_forward(
                params, cfg, tok, p, cache, table, p + 1, blocks, p % T)
            return (logits, cache), tok

        (logits, cache), toks = jax.lax.scan(
            step, (logits, cache), jnp.arange(3))
        return toks, logits, cache

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = jax.jit(decode_scan, donate_argnums=(3,)).lower(
        params, sds((batch, cfg.vocab_size), cfg.dtype),
        sds((batch,), jnp.int32), cache, sds((batch, 1024), jnp.int32),
    ).compile()
    text = compiled.as_text()
    E, d, f = cfg.n_experts, cfg.dim, cfg.moe_ffn_dim
    leaves = {f"bf16[{E},{d},{f}]", f"bf16[{E},{f},{d}]"}
    found = _INSTRUCTION.findall(text)
    assert len(found) > 100, "the optimized program did not parse"
    copies = [f"{n} = {shape} {op}" for n, shape, op in found
              if shape in leaves and op not in (
                  "parameter", "bitcast", "get-tuple-element")]
    assert not copies, copies
    assert not _slab_copies(text, pc.n_kv_heads, pc)
    mem = compiled.memory_analysis()
    cache_bytes = int(np.prod(cache.shape)) * cache.dtype.itemsize
    assert mem.alias_size_in_bytes >= cache_bytes, mem
    # the gathered rows of 8 x 1,024 pages and the vocabulary's logits, not
    # a second cache and not a copy of the experts (1.2 GB a layer)
    assert mem.temp_size_in_bytes < 800 << 20, mem


@functools.cache
def _window_full_scan_on_tpu(device, batch=8, width=2048):
    """The window/full parallel-block family's decode scan at its published
    widths (one period of four layers, 16 of 128 experts, the benchmark's
    two pools of 10,240 and 8,192 blocks, batch 8 over tables of 2,048
    pages), compiled for one described chip, once for the tests that read
    it.  -> (cfg, pc, the parameters' abstract arrays, compiled)"""
    cfg = models.Cohere2MoeConfig(n_layers=4, n_experts_held=16, vocab_size=32768)
    pc = PagedCacheConfig.for_model(cfg, 10240, T, window_blocks=8192)
    chip = SingleDeviceSharding(device)
    params = _shaped(jax.eval_shape(
        lambda: models.init_cohere2_moe_params(cfg, jax.random.PRNGKey(0))), chip)
    cache = _shaped(jax.eval_shape(lambda: init_cache(pc)), chip)

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jax.tree.map(lambda t: jnp.take_along_axis(
                t, (p // T)[:, None], axis=1)[:, 0], table)
            logits, cache, n = models.cohere2_moe_decode_forward(
                params, cfg, tok, p, cache, table, p + 1, blocks, p % T)
            return (logits, cache), (tok, n)

        (logits, cache), (toks, n) = jax.lax.scan(
            step, (logits, cache), jnp.arange(3))
        return toks, n.sum(), logits, cache

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = jax.jit(decode_scan, donate_argnums=(3,)).lower(
        params, sds((batch, cfg.vocab_size), cfg.dtype),
        sds((batch,), jnp.int32), cache,
        (sds((batch, width), jnp.int32), sds((batch, width), jnp.int32)),
    ).compile()
    return cfg, pc, params, compiled


def test_window_layers_on_tpu_gather_their_window_and_no_expert_leaf_is_copied(v5e):
    """The window/full parallel-block family at its published widths (one
    period of four layers, 16 of 128 experts, the benchmark's two pools of
    10,240 and 8,192 blocks, batch 8 over tables of 2,048 pages): in the
    decode scan a window layer gathers ``window_page_span`` = 257 pages a row
    out of its pool and NO layer its table's 2,048 (a window layer that
    gathered the table to mask seven eighths of it would show gathers of the
    table's shape; the full layer's were there until PR 36 and are the
    kernel's live pages now); no held expert leaf is copied; both pools that
    come out are the donated ones."""
    from infinistore_tpu.models.attention import window_page_span

    batch, width = 8, 2048
    cfg, pc, _, compiled = _window_full_scan_on_tpu(v5e[0], batch, width)
    span = window_page_span(cfg.sliding_window, T)
    assert span == 257
    text = compiled.as_text()
    found = _INSTRUCTION.findall(text)
    assert len(found) > 100, "the optimized program did not parse"
    Eh, d, f = cfg.n_experts_held, cfg.dim, cfg.ffn_dim
    copies = [f"{n} = {shape} {op}" for n, shape, op in found
              if shape in (f"bf16[{Eh},{d},{f}]", f"bf16[{Eh},{f},{d}]")
              and op not in ("parameter", "bitcast", "get-tuple-element")]
    assert not copies, copies
    # gathered pages by the number of (row, page) pairs they hold: K and V of
    # three window layers at batch x span, of no layer at batch x width
    shape = lambda n: f"bf16[{n},{cfg.n_kv_heads},{T},{cfg.head_dim}]"
    pages = lambda n: [op for _, s, op in found if s == shape(n)
                       and op in ("gather", "fusion")]
    assert not pages(batch * width), pages(batch * width)
    assert len(pages(batch * span)) >= 2 * 3, pages(batch * span)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pc.cache_bytes, mem


@pytest.mark.parametrize("batch,h_kv,group", [(32, 4, 7), (8, 8, 4), (2, 1, 20)],
                         ids=["qwen2.5-32-rows", "qwen3-8-rows", "jamba-2-rows"])
def test_decode_kernel_alone_on_tpu_holds_its_static_run_twice_and_two_waits(
        batch, h_kv, group, v5e, monkeypatch, capsys):
    """The kernel ALONE at the dense cells' heads (and Jamba's one KV head of
    20 query heads) and a table of 256 pages:
    the TPU's compiler takes its whole blocks' static run of copy starts and
    their ONE wait on a descriptor as large as a slot, and what it costs to
    lower is held by what the Mosaic module holds: the run of
    ``PAGES_PER_BLOCK`` starts at the two places that start a block (the
    first, and the prefetch of the next block or the next row's first) beside
    each place's own loop of one, 66 starts, and two waits (a whole block's
    one, the last block's loop of one).  The parent's module held 3 and 1;
    copies unrolled by the page at all four places that start or wait took
    seven times the parent's seconds to lower and compile (PR 36), where this
    takes 0.58 / 0.69 s against 0.49 / 0.61 (by hand, PR 53, no chip)."""
    from jax.experimental import pallas as pl

    from infinistore_tpu.models import paged_decode_kernel

    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, debug=True))
    chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    # ``_call`` under a jit of this test's own: whatever this process has
    # traced before, the kernel is traced here, and prints its module
    compiled = jax.jit(paged_decode_kernel._call.__wrapped__).lower(
        sds((batch, h_kv, group, 128), jnp.bfloat16),
        sds((12, 2, h_kv, 4096, T, 128), jnp.bfloat16),
        sds((batch, 256), jnp.int32), sds((batch,), jnp.int32),
        sds((1,), jnp.int32)).compile()
    assert len(_kernel_calls(compiled.as_text())) == 1
    module = capsys.readouterr().out
    starts = len(re.findall(r"\btpu\.enqueue_dma\b", module))
    waits = len(re.findall(r"\btpu\.wait_dma", module))
    assert (starts, waits) == (
        2 * (paged_decode_kernel.PAGES_PER_BLOCK + 1), 2), (starts, waits)


def test_full_layer_on_tpu_reads_live_pages_through_the_kernel(v5e):
    """The same program (groups of 16 query heads over 8 KV heads, 8 rows x
    2,048 pages): the one full layer's attention is one call of the kernel
    and nothing holds the elements of its gathered table ([B * pages, H_kv,
    T, D], or the layout copy ``bf16[8,2048,16,8,128]`` that was 18% of this
    cell's scan: PERF.md, PR 35), in any order of dimensions; the decode
    scan's temporaries are under the 2.22 GB they were with it."""
    batch, width = 8, 2048
    cfg, pc, params, compiled = _window_full_scan_on_tpu(v5e[0], batch, width)
    text = compiled.as_text()
    assert len(_kernel_calls(text)) == 1
    # a held expert leaf, [16, 4096, 4096], has as many elements as the table
    tables = _bf16_results_of(
        text, batch * width * T * cfg.n_kv_heads * cfg.head_dim,
        _weight_shapes(params))
    assert not tables, tables
    # (a slab of the full layer's pool of ONE layer is that pool, written in
    # place; the window layers' pool of three has slabs, and none is copied)
    window_pool = PagedCacheConfig(
        n_layers=len(pc.window_layers), n_kv_heads=pc.n_kv_heads,
        head_dim=pc.head_dim, n_blocks=pc.window_blocks, block_tokens=T)
    assert not _slab_copies(text, pc.n_kv_heads, window_pool)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_pages_of_two_shapes_on_tpu_lie_unpadded_and_no_program_copies_a_pool(v5e):
    """The window/full family whose layer kinds write pages of two shapes
    (models/mimo_v2.py) at its published widths (the first 7 layers, 16 of 256
    experts, the benchmark's pools of 10,240 and 512 blocks): each pool lies
    on the device in the bytes the count gives it (a row of 4 x 320 or 8 x
    320 values is whole 128-lane tiles: 5,120 B a token in the two full
    layers' pool, 25,600 B in the five window layers'), the decode scan
    (batch 8 over tables of 2,048 pages) and a prefill chunk over the largest
    prefix buffers (the full layers' 16,384 rows, the window layers' 128)
    copy NO pool, and the pools that come out of the scan are the donated
    ones."""
    from infinistore_tpu.models import mimo_v2

    cfg = mimo_v2.MimoV2Config(
        n_layers=7, n_experts_held=16, vocab_size=19072,
        layer_pattern=(0, 1, 1, 1, 1, 0, 1), moe_layers=(0, 1, 1, 1, 1, 1, 1))
    pc = PagedCacheConfig.for_model(cfg, 10240, T, window_blocks=512)
    assert pc.cache_bytes == 10240 * T * 5120 + 512 * T * 25600
    chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    params = _shaped(jax.eval_shape(
        lambda: mimo_v2.init_mimo_v2_params(cfg, jax.random.PRNGKey(0))), chip)
    cache = _shaped(jax.eval_shape(lambda: init_cache(pc)), chip)
    batch, width = 8, 2048

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jax.tree.map(lambda t: jnp.take_along_axis(
                t, (p // T)[:, None], axis=1)[:, 0], table)
            logits, cache, n = mimo_v2.mimo_v2_decode_forward(
                params, cfg, tok, p, cache, table, p + 1, blocks, p % T)
            return (logits, cache), (tok, n)

        (logits, cache), (toks, n) = jax.lax.scan(
            step, (logits, cache), jnp.arange(3))
        return toks, n.sum(), logits, cache

    scan = jax.jit(decode_scan, donate_argnums=(3,)).lower(
        params, sds((batch, cfg.vocab_size), cfg.dtype),
        sds((batch,), jnp.int32), cache,
        (sds((batch, width), jnp.int32), sds((batch, width), jnp.int32)),
    ).compile()
    # the pools as the device lays them out: the count's bytes, not a tile more
    assert scan.memory_analysis().alias_size_in_bytes == pc.cache_bytes
    prefix = (sds((2, 1, 1, 16384, 1, 1280), cfg.dtype),
              sds((5, 1, 1, 128, 1, 2560), cfg.dtype))
    chunk = jax.jit(lambda p, t, kv, n: mimo_v2.mimo_v2_prefill_forward(
        p, cfg, t, prefix_kv=kv, prefix_len=n, head="none")).lower(
        params, sds((1, 512), jnp.int32), prefix, sds((), jnp.int32)).compile()
    for compiled in (scan, chunk):
        text = compiled.as_text()
        assert len(_INSTRUCTION.findall(text)) > 100
        copies = [c for pool in cache for c in _whole_cache_results(text, pool.shape)]
        assert not copies, copies
    # a chunk's scores: one group of 16 query heads over 16.9k keys in a full
    # layer, a band of 128 + 512 keys in a window layer
    assert chunk.memory_analysis().temp_size_in_bytes < 700 << 20


def test_retention_decode_scan_on_tpu_moves_each_rows_state_in_place(v5e):
    """The power-retention family at its published widths (two layers, the
    benchmark's 16 state slots, 8 rows): the decode scan's slots that come out
    are the donated ones, and what is live beside them is a row's state of a
    layer and the vocabulary's logits, not every row's state gathered at once
    (270 MB a layer at 8 rows; the compiler kept several layers' in flight,
    4 GB) and not a second copy of the slots."""
    from infinistore_tpu.kv.cache import StateCacheConfig

    cfg = models.RetentionConfig(n_layers=2)
    pc = StateCacheConfig.for_model(cfg, 4096, T, 4096, max_rows=8)
    assert (pc.n_slots, pc.page_bytes) == (16, 34080768)
    chip = SingleDeviceSharding(v5e[0])
    params = _shaped(jax.eval_shape(
        lambda: models.init_retention_params(cfg, jax.random.PRNGKey(0))), chip)
    cache = _shaped(jax.eval_shape(lambda: init_cache(pc)), chip)
    batch = 8

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits, cache = models.retention_decode_forward(
                params, cfg, tok, pos + i, cache, table, None, None, None)
            return (logits, cache), tok

        (logits, cache), toks = jax.lax.scan(
            step, (logits, cache), jnp.arange(3))
        return toks, logits, cache

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = jax.jit(decode_scan, donate_argnums=(3,)).lower(
        params, sds((batch, cfg.vocab_size), cfg.dtype),
        sds((batch,), jnp.int32), cache, sds((batch, 1), jnp.int32),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pc.cache_bytes, mem
    assert mem.temp_size_in_bytes < 5 * pc.page_bytes * 2, mem


def test_hybrid_decode_scan_on_tpu_reads_heads_of_64_through_the_kernel(v5e):
    """The short-convolution / attention family at its published widths (its
    first six layers: one attention layer of 32 query heads over 8 key/value
    heads of 64, five conv layers; 8 rows x 2,048 pages).  A lone head of 64
    does not lower in the kernel (the compiler's words below), so the page
    holds the heads side by side in pairs: the attention layer is ONE call of
    the kernel, no gathered table exists (4.3 GB of temporaries at 1,024
    pages with the XLA form), and pages and state slots that come out are the
    donated ones."""
    from infinistore_tpu.kv.cache import HybridCacheConfig
    from infinistore_tpu.models import attention, paged_decode_kernel

    chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    batch, width = 8, 2048
    lone = jax.jit(functools.partial(
        paged_decode_kernel.paged_decode_attention_kernel, layer=0)).lower(
        sds((batch, 32, 64), jnp.bfloat16),
        sds((1, 2, 8, N_BLOCKS, T, 64), jnp.bfloat16),
        sds((batch, width), jnp.int32), sds((batch,), jnp.int32))
    with pytest.raises(Exception, match=r"aligned to tiling \(128\), but is 64"):
        lone.compile()

    cfg = models.Lfm2MoeConfig(n_layers=6, layer_types=(
        "conv", "conv", "full_attention", "conv", "conv", "conv"))
    pc = HybridCacheConfig.for_model(cfg, 10240, T, 512, max_rows=batch)
    assert (pc.n_kv_heads, pc.head_dim, pc.page_bytes) == (4, 128, 32768)
    assert attention.decode_kernel_engages(
        jax.ShapeDtypeStruct((batch, 32, 64), cfg.dtype),
        jax.ShapeDtypeStruct((1, 2, 4, 10240, T, 128), cfg.dtype))
    params = _shaped(jax.eval_shape(
        lambda: models.init_lfm2_moe_params(cfg, jax.random.PRNGKey(0))), chip)
    cache = _shaped(jax.eval_shape(lambda: init_cache(pc)), chip)

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jnp.take_along_axis(table[0], (p // T)[:, None], axis=1)[:, 0]
            logits, cache = models.lfm2_moe_decode_forward(
                params, cfg, tok, p, cache, table, p + 1,
                (blocks, table[1][:, 0]), p % T)
            return (logits, cache), tok

        (logits, cache), toks = jax.lax.scan(
            step, (logits, cache), jnp.arange(3))
        return toks, logits, cache

    compiled = jax.jit(decode_scan, donate_argnums=(3,)).lower(
        params, sds((batch, cfg.vocab_size), cfg.dtype),
        sds((batch,), jnp.int32), cache,
        (sds((batch, width), jnp.int32), sds((batch, 1), jnp.int32)),
    ).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text)) == 1
    tables = _bf16_results_of(
        text, batch * width * T * cfg.n_kv_heads * cfg.head_dim,
        _weight_shapes(params))
    assert not tables, tables
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pc.cache_bytes, mem
    assert mem.temp_size_in_bytes < 256 << 20, mem


def test_mamba_attention_programs_on_tpu_hold_both_kernels_and_copy_no_slots(v5e):
    """The Mamba-1 / attention family at its published sizes, all 28 layers
    (models/jamba.py: ONE ``lax.scan`` over the 26 Mamba layers' stacked
    leaves, the two attention layers of 20 query heads over ONE key/value head
    of 128 under a ``lax.switch`` in its body), beside its cell's cache: 10,240 blocks of pages
    and 320 float32 slots of 10.1 MB.  The selective-scan kernel lowers for
    the chip alone; the decode kernel takes a group of 20 query heads (no
    multiple of 8 sublanes) over one key/value head; the decode scan at 8
    rows x 1,024 pages holds it twice and gathers no table; the prefill chunk
    over a 16k prefix holds the scan kernel ONCE; and neither program
    copies the slots (with the layer axis on the sublanes each did, twice a
    launch: 3.2 GB, kv/cache.py ``state_lanes``) nor, by the memory analysis,
    a layer's matrices out of a run's stack."""
    from infinistore_tpu.kv.cache import HybridCacheConfig
    from infinistore_tpu.models import attention, ssm_scan

    chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    f32 = lambda *shape: sds(shape, jnp.float32)
    batch, width, chunk, prefix = 8, 1024, 512, 16384
    cfg = models.JambaConfig()
    N, di = cfg.d_state, cfg.d_inner
    assert ssm_scan.kernel_engages(chunk, di, N)
    alone = jax.jit(ssm_scan.selective_scan_kernel).lower(
        f32(chunk, di), f32(chunk, di), f32(chunk, N), f32(chunk, N),
        f32(N, di), f32(N, di)).compile().as_text()
    assert "ssm_selective_scan" in alone

    pc = HybridCacheConfig.for_model(cfg, 10240, T, 512, max_rows=batch)
    assert (pc.n_kv_heads, pc.head_dim, pc.page_bytes) == (1, 128, 8192)
    assert attention.decode_kernel_engages(
        jax.ShapeDtypeStruct((batch, 20, 128), cfg.dtype),
        jax.ShapeDtypeStruct((2, 2, 1, 10240, T, 128), cfg.dtype))
    params = _shaped(jax.eval_shape(
        lambda: models.init_jamba_params(cfg, jax.random.PRNGKey(0))), chip)
    cache = _shaped(jax.eval_shape(lambda: init_cache(pc)), chip)
    slots = "f32[%s]" % ",".join(map(str, cache[1].shape))
    weights = sum(w.size * w.dtype.itemsize for w in jax.tree.leaves(params))

    def slot_copies(text):
        return [f"{name} = {shape} {op}"
                for name, shape, op in _INSTRUCTION.findall(text)
                if shape == slots and op in ("copy", "transpose")]

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jnp.take_along_axis(table[0], (p // T)[:, None], axis=1)[:, 0]
            logits, cache = models.jamba_decode_forward(
                params, cfg, tok, p, cache, table, p + 1,
                (blocks, table[1][:, 0]), p % T)
            return (logits, cache), tok

        (logits, cache), toks = jax.lax.scan(
            step, (logits, cache), jnp.arange(3))
        return toks, logits, cache

    compiled = jax.jit(decode_scan, donate_argnums=(3,)).lower(
        params, sds((batch, cfg.vocab_size), cfg.dtype),
        sds((batch,), jnp.int32), cache,
        (sds((batch, width), jnp.int32), sds((batch, 1), jnp.int32)),
    ).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text)) == 2
    tables = _bf16_results_of(
        text, batch * width * T * cfg.n_kv_heads * cfg.head_dim,
        _weight_shapes(params))
    assert not tables, tables
    assert not slot_copies(text), slot_copies(text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pc.cache_bytes, mem
    # a layer copied out of the stack would be 208 MB at a time, the pages
    # copied through the switch 168 MB
    assert mem.temp_size_in_bytes < 192 << 20, mem
    assert mem.argument_size_in_bytes >= weights + pc.cache_bytes

    def chunk_fn(p, t, conv, slot, n, buf, plen):
        return models.jamba_prefill_forward(
            p, cfg, t, conv, slot, n, prefix_kv=buf, prefix_len=plen,
            head="none")

    i32 = sds((), jnp.int32)
    compiled = jax.jit(chunk_fn, donate_argnums=(2,)).lower(
        params, sds((1, chunk), jnp.int32), cache[1], i32, i32,
        sds((2, 2, 1, prefix, 1, 128), cfg.dtype), i32).compile()
    text = compiled.as_text()
    # its result is a pair (y, the state): counted by the line
    scans = [line.split(" = ")[0].strip() for line in text.splitlines()
             if " custom-call(" in line and "%ssm_selective_scan" in line]
    assert len(scans) == 1, scans              # one body for 26 layers
    assert not slot_copies(text), slot_copies(text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pc.n_slots * pc.slot_bytes, mem
    # the two attention layers' scores over 16.9k keys are the most of it
    assert mem.temp_size_in_bytes < 640 << 20, mem


# the largest push of each cell: a 512-token chunk's 32 pages of every layer
# (the cells' caches as BENCHMARK.json's configurations size them), and
# what the TPU compiler says the one program keeps live beside its bands
_PUSH_CELLS = {
    # cell: (pools of (layers, planes, heads, blocks, width), the window
    #        layers of a stack with a pool a layer kind and the pages each
    #        pool sends, temporaries allowed, bytes of the bands)
    "qwen2.5-7b-l12": ([(12, 2, 4, 12288, 128)], None, 1 << 20, 12582912),
    "qwen3-8b-l12": ([(12, 2, 8, 6144, 128)], None, 1 << 20, 25165824),
    # the full layer's pool, then the three window layers'
    "command-a-plus-l4-e16": ([(1, 2, 8, 10240, 128), (3, 2, 8, 8192, 128)],
                              ((0, 1, 2), (32, 32)), 1 << 20, 8388608),
    # pages of two shapes: the two full layers' rows of 4 x 320 send every
    # page of the chunk, the five window layers' rows of 8 x 320 the last 8
    "mimo-v2-flash-l7-e16": ([(2, 1, 1, 10240, 1280), (5, 1, 1, 512, 2560)],
                             ((1, 2, 3, 4, 6), (32, 8)), 1 << 20,
                             2 * 32 * 40960 + 5 * 8 * 81920),
    # the latent page: ONE copy of the whole cache, 1.51 GB padded to
    # [16, 640]-wide tiles, which the bare gather by ids has too (below)
    "kanana-2-30b-a3b-l8": ([(8, 1, 1, 10240, 576)], None, 1700 << 20,
                            5242880),
}


def _whole_cache_results(text, shape):
    want = "bf16[%s]" % ",".join(map(str, shape))
    return [f"{name} = {s} {op}" for name, s, op in _INSTRUCTION.findall(text)
            if s == want
            and op not in ("parameter", "bitcast", "get-tuple-element")]


@pytest.mark.parametrize("cell", list(_PUSH_CELLS))
def test_push_program_on_tpu_adds_no_copy_of_the_cache(cell, v5e):
    """The push's one program (gather by ids, the store's layout, the layer
    bands: ``kv/transfer.py:_gather_bands``) at each cell's cache and a
    512-token chunk's 32 pages: its temporaries, and that no instruction's
    result is a whole pool of the cache, but for the latent cache, where the
    gather by ids alone (the program the eager path launched first,
    ``jit_gather``: 5 ms a push on the chip) already re-lays the cache out
    once (its default layout puts the blocks innermost, PERF.md section 5):
    the one program has that ONE copy and adds none."""
    from infinistore_tpu.kv import read_pages
    from infinistore_tpu.kv.transfer import (
        KVTransferEngine,
        _gather_bands,
        _gather_bands_by_pool,
    )

    pools, by_kind, temp_limit, band_bytes = _PUSH_CELLS[cell]
    chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    shapes = [(n, planes, heads, blocks, T, width)
              for n, planes, heads, blocks, width in pools]
    caches = tuple(sds(s, jnp.bfloat16) for s in shapes)
    if by_kind is None:
        caches = caches[0]
        compiled = _gather_bands.lower(
            caches, sds((32,), jnp.int32), False, 4).compile()
    else:
        # a pool a layer kind: the bands the transfer engine plans, in stack
        # order, each gathered from its own pool (of its own page shape)
        window_layers, sent = by_kind
        pc = PagedCacheConfig(
            n_layers=sum(n for n, *_ in pools), n_kv_heads=pools[0][2],
            head_dim=pools[0][4], n_blocks=pools[0][3], block_tokens=T,
            planes=pools[0][1], window_layers=window_layers,
            window_blocks=pools[1][3])
        plan = tuple((p, l0, len(ls)) for p, l0, ls in
                     KVTransferEngine(None, pc)._band_plan(list(sent)))
        ids = tuple(sds((n,), jnp.int32) for n in sent)
        compiled = _gather_bands_by_pool.lower(caches, ids, plan, False).compile()
    mem = compiled.memory_analysis()
    assert band_bytes <= mem.output_size_in_bytes < band_bytes + 4096, mem
    assert mem.temp_size_in_bytes < temp_limit, mem
    text = compiled.as_text()
    copies = [c for s in shapes for c in _whole_cache_results(text, s)]
    if cell != "kanana-2-30b-a3b-l8":
        assert not copies, copies
        return
    bare = jax.jit(read_pages).lower(caches, sds((32,), jnp.int32)).compile()
    assert len(copies) == len(_whole_cache_results(bare.as_text(),
                                                   shapes[0])) == 1, copies
    assert mem.temp_size_in_bytes <= (
        bare.memory_analysis().temp_size_in_bytes + (1 << 20))


def test_state_push_program_on_tpu_forms_no_whole_slot_beside_its_bands(v5e):
    """Brumby's largest push, a slot of 272.6 MB in four bands of two layers:
    each band is laid out from its own layers of the slot, so what is live
    beside the bands is a band's worth (68 MB), where the slot gathered and
    concatenated first was another 272.6 MB and its slices a third."""
    from infinistore_tpu.kv.cache import StateCacheConfig
    from infinistore_tpu.kv.transfer import _state_to_wire

    cfg = models.RetentionConfig(n_layers=8)
    pc = StateCacheConfig.for_model(cfg, 4096, T, 4096, max_rows=8)
    chip = SingleDeviceSharding(v5e[0])
    S, z = _shaped(jax.eval_shape(lambda: init_cache(pc)), chip)
    compiled = _state_to_wire.lower(
        S, z, jax.ShapeDtypeStruct((), jnp.int32, sharding=chip), 4).compile()
    mem = compiled.memory_analysis()
    assert pc.slot_bytes <= mem.output_size_in_bytes < pc.slot_bytes + (1 << 16)
    assert mem.temp_size_in_bytes < pc.slot_bytes // 4 + (1 << 20), mem
