"""The Pallas kernels compile for a described TPU v5e at real widths.

Nothing runs: the TPU compiler, which is installed without a chip,
compiles each kernel for one chip of a described ``v5e:2x2`` host. This
catches what interpret mode cannot — blocks the tiling refuses and blocks
that overflow the scoped VMEM — before a chip run does. Widths are
Qwen2-0.5B's (14/2 heads, head_dim 64) for attention, Mixtral-8x22B's
(d 6144, F 16384, 8 experts) for the grouped GEMM, RWKV-6 1.6B's (head
64) for the scan.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (decode_attention, flash_attention, matmul,
                           moe_gemm, rwkv_scan)

H, KV, D = 14, 2, 64  # Qwen2-0.5B attention
PAGE, PAGES_PER_SEQ, BATCH = 16, 35, 8
N_PAGES = 1 + BATCH * PAGES_PER_SEQ


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 host, with the persistent
    compilation cache off (a cross-compile cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for the described chip; return its HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


PAGE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


def _paged_shapes(page_dtype, q_shape, rows):
    qd = jnp.bfloat16 if page_dtype == jnp.bfloat16 else jnp.float32
    shapes = [(q_shape, qd),
              ((N_PAGES, PAGE, KV, D), page_dtype),
              ((N_PAGES, PAGE, KV, D), page_dtype),
              ((rows, PAGES_PER_SEQ), jnp.int32), ((rows,), jnp.int32)]
    if page_dtype == jnp.int8:
        shapes += [((N_PAGES, PAGE, KV), jnp.float32)] * 2
    return shapes


def _paged_call(kernel):
    def call(q, kp, vp, table, pos, ks=None, vs=None):
        return kernel(q, kp, vp, table, pos, k_scale=ks, v_scale=vs)
    return call


@pytest.mark.parametrize("pages", sorted(PAGE_DTYPES))
def test_paged_decode(chip, pages):
    hlo = _compile(chip, _paged_call(decode_attention.paged_decode_attention),
                   *_paged_shapes(PAGE_DTYPES[pages], (BATCH, H, D), BATCH))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pages,span", [("f32", 128), ("bf16", 8),
                                        ("int8", 8)])
def test_paged_span(chip, pages, span):
    """span 128 is the served prefill chunk; 8 a speculative verify."""
    hlo = _compile(chip,
                   _paged_call(decode_attention.paged_decode_span_attention),
                   *_paged_shapes(PAGE_DTYPES[pages], (1, span, H, D), 1))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("window,dtype", [(1024, jnp.float32),
                                          (32768, jnp.bfloat16)])
def test_dense_decode_block_fits_vmem(chip, window, dtype):
    """The default block_k=512 overflowed the 16 MiB scoped VMEM here
    (f32 at window 1024, bf16 at 32768); the derived block fits."""
    assert decode_attention.dense_block_k(window, KV, D, dtype) == 256
    hlo = _compile(chip, lambda q, k, v, p: decode_attention.decode_attention(
        q, k, v, p), ((BATCH, H, D), dtype), ((BATCH, window, KV, D), dtype),
        ((BATCH, window, KV, D), dtype), ((BATCH,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("x_dtype,w_dtype", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.bfloat16, jnp.int8),
    (jnp.float32, jnp.float32)])
def test_grouped_gemm_mixtral(chip, x_dtype, w_dtype):
    """f32 at block_f=512 overflowed VMEM (a full-depth 6144-row weight
    tile); the derived block_f fits."""
    m, d, f, e = 64 * 16, 6144, 16384, 8
    shapes = [((m, d), x_dtype), ((e, d, f), w_dtype), ((16,), jnp.int32)]
    if w_dtype == jnp.int8:
        shapes.append(((e,), jnp.float32))
    hlo = _compile(chip, lambda x, w, g, s=None: moe_gemm.grouped_matmul(
        x, w, g, w_scale=s), *shapes)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(chip, dtype):
    s = [((4 * H, 512, D), dtype)] * 3
    hlo = _compile(chip, lambda q, k, v: flash_attention.flash_attention(
        q, k, v), *s)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("window,precision", [
    (None, "default"), (1024, "default"), (None, "highest")])
def test_flash_attention_train(chip, window, precision):
    """The training attention of Qwen2-0.5B at train_4k: forward and both
    backward kernels of one layer and microbatch (14 heads, S 4096, f32),
    at the blocks the VMEM count picks: 1024 x 1024 for bf16 MXU
    operands at the default precision (the trainer's); 512 x 512 for f32
    operands under "highest", which overflow the scoped VMEM at 1024."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert flash_attention.flash_block(4096, D, f32, bf16) == 1024
    assert flash_attention.flash_block(4096, D, f32, f32) == 512

    def fwd_bwd(q, k, v, do):
        o, back = jax.vjp(lambda q, k, v: flash_attention.flash_attention(
            q, k, v, window=window), q, k, v)
        return (o,) + back(do)

    with jax.default_matmul_precision(precision):
        hlo = _compile(chip, fwd_bwd, *[((H, 4096, D), jnp.float32)] * 4)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in hlo, name


def test_rwkv_scan(chip):
    """Needed a (1, 1, hd) block for u and no in-kernel cumsum or
    negative indexing, which Mosaic does not lower."""
    bh, s, hd = 4 * 32, 512, 64
    hlo = _compile(chip, rwkv_scan.rwkv_wkv,
                   *[((bh, s, hd), jnp.float32)] * 4,
                   ((bh, hd), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_fbist_matmul(chip):
    hlo = _compile(chip, lambda a, b: matmul.matmul(a, b),
                   ((128, 128), jnp.float32), ((128, 128), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["flash", "grouped", "matmul"])
def test_bf16_kernels_under_highest_precision(chip, kernel):
    """A process-wide "highest" (fp32 contract precision) must not reach
    dots on bf16 operands, which Mosaic refuses ("Bad lhs type")."""
    bf = jnp.bfloat16
    calls = {
        "flash": (lambda q, k, v: flash_attention.flash_attention(q, k, v),
                  [((4 * H, 512, D), bf)] * 3),
        "grouped": (lambda x, w, g: moe_gemm.grouped_matmul(x, w, g),
                    [((1024, 6144), bf), ((8, 6144, 16384), bf),
                     ((16,), jnp.int32)]),
        "matmul": (lambda a, b: matmul.matmul(a, b),
                   [((512, 512), bf)] * 2),
    }
    fn, shapes = calls[kernel]
    with jax.default_matmul_precision("highest"):
        assert "tpu_custom_call" in _compile(chip, fn, *shapes)
