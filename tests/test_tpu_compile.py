"""The paged-decode kernels compile for a TPU v5e chip at published widths.

Every other kernel test runs in interpret mode on the CPU, which never meets
the TPU compiler's rules (block shapes, tiling, VMEM). Here each kernel is
lowered with ``interpret=False`` for a *described* v5e chip — no chip is
attached; libtpu's compiler runs on the host — and the compiled program must
contain the Mosaic kernel (``tpu_custom_call``). Shapes come from the configs
the serving path runs: OPT-1.3B (MHA, f32) and Qwen3-4B (GQA, bf16).

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and a test file that loaded it while being
collected would break the other test workers. JAX's persistent compilation
cache is off around these tests (an entry compiled for a described chip
cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_decode import (paged_decode_gqa_pallas,
                                        paged_decode_pallas)

B, PAGE, P = 4, 16, 32          # decode batch, page size, pages per sequence


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(sharding, kernel, arch, *, int8=False, **kw):
    cfg = get_config(arch)
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    n_pages = B * P + 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    kv = spec((n_pages, Hkv, PAGE, Dh), jnp.int8 if int8 else dt)
    args = [spec((B, H, Dh), dt), kv, kv, spec((B, P), jnp.int32),
            spec((B,), jnp.int32)]
    if int8:
        args += [spec((n_pages, Hkv, PAGE), dt)] * 2
    fn = jax.jit(lambda *a: kernel(*a, interpret=False, **kw))
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("normalize", [True, False])
def test_mha_decode_compiles_at_opt_1_3b(one_chip, normalize):
    txt = _compiled_text(one_chip, paged_decode_pallas, "opt-1.3b",
                         normalize=normalize)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("pages_per_block", [1, 2])
def test_gqa_decode_compiles_at_qwen3_4b(one_chip, pages_per_block):
    txt = _compiled_text(one_chip, paged_decode_gqa_pallas, "qwen3-4b",
                         pages_per_block=pages_per_block)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("arch,kernel", [
    ("opt-1.3b", paged_decode_pallas),
    ("qwen3-4b", paged_decode_gqa_pallas),
])
def test_int8_pool_compiles_at_page_16(one_chip, arch, kernel):
    # int8 tiles are (32, 128), not f32's (8, 128): a 16-row page block is
    # legal only because it spans the pool's whole minor dims
    txt = _compiled_text(one_chip, kernel, arch, int8=True)
    assert "tpu_custom_call" in txt
