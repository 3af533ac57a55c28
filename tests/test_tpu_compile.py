"""The paged-decode and flash-attention kernels compile for a TPU v5e chip at
published widths.

Every other kernel test runs in interpret mode on the CPU, which never meets
the TPU compiler's rules (block shapes, tiling, VMEM). Here each kernel is
lowered with ``interpret=False`` for a *described* v5e chip — no chip is
attached; libtpu's compiler runs on the host — and the compiled program must
contain the Mosaic kernel (``tpu_custom_call``). Shapes come from the configs
the serving path runs: OPT-1.3B (MHA, f32) and Qwen3-4B (GQA, bf16); the
flash-attention kernel at the search's 512-token calibration rows. The
no-cache forward compiles for one chip with the kernel, and for the four
chips of a v5e:2x2 host with a batch-sharded input, where GSPMD could not
split the kernel and the forward keeps ``blocked_attention``. Moonlight's
grouped MoE layer compiles to a conditional over its dispatch's row bounds.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and a test file that loaded it while being
collected would break the other test workers. JAX's persistent compilation
cache is off around these tests (an entry compiled for a described chip
cannot be read back without one).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import obs
from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models import forward, init_params
from repro.models import layers as L
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
def v5e_2x2(no_compile_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


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


@pytest.mark.parametrize("arch,S,causal", [
    ("opt-1.3b", 512, True),        # two heads of 64 per lane block
    ("opt-1.3b", 1024, False),
    ("opt-1.3b", 1024, True),       # the longest sequence it takes
    ("opt-1.3b", 384, True),        # 128-row q tiles
    ("qwen3-4b", 512, True),        # GQA, one head of 128 per lane block
])
def test_flash_attention_compiles(one_chip, arch, S, causal):
    cfg = get_config(arch)
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def spec(heads):
        return jax.ShapeDtypeStruct((2, S, heads * Dh), jnp.bfloat16,
                                    sharding=one_chip)

    fn = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=causal, head_dim=Dh))
    txt = fn.lower(spec(H), spec(Hkv), spec(Hkv)).compile().as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("program,path", [
    ("one_chip", "flash"),          # a host with one chip
    ("batch_sharded", "blocked"),   # GSPMD over a host's four chips
    ("shard_map", "flash"),         # one chip per shard_map body
])
def test_forward_compiles_for_its_devices(v5e_2x2, monkeypatch, program,
                                          path):
    # trace as a TPU host would: TPU routing, Mosaic lowering, its devices
    n_chips = 1 if program == "one_chip" else len(v5e_2x2.devices)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "device_count", lambda: n_chips)
    cfg = dataclasses.replace(get_config("opt-tiny"), n_layers=2)
    mesh = jax.sharding.Mesh(np.array(v5e_2x2.devices), ("data",))
    if program == "one_chip":
        rows = whole = SingleDeviceSharding(v5e_2x2.devices[0])
    else:
        rows = NamedSharding(mesh, PartitionSpec("data"))
        whole = NamedSharding(mesh, PartitionSpec())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    toks = jax.ShapeDtypeStruct((8, 128), jnp.int32, sharding=rows)

    def fwd(p, t):
        return forward(p, cfg, t)

    if program == "shard_map":
        fwd = jax.shard_map(
            fwd, mesh=mesh, in_specs=(PartitionSpec(), PartitionSpec("data")),
            out_specs=PartitionSpec("data"), check_vma=False)
    c = obs.counter("attention_path_total")
    before = c.value(path=path)
    txt = jax.jit(fwd).lower(params, toks).compile().as_text()
    assert c.value(path=path) == before + 1
    assert ("tpu_custom_call" in txt) == (path == "flash")


def test_moonlight_dispatch_compiles_to_a_ladder_of_bounds(one_chip,
                                                           monkeypatch):
    """Moonlight's grouped MoE layer at its widths (8 of 64 experts held,
    top-6, 16 x 512 tokens) compiles to a conditional whose branches gather
    7,680, 15,360 and 49,152 rows of 2,048: 1.25x the held experts' even
    share of 6,144 slots rounded up to 512, twice that, and every slot."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    base = get_config("moonshot-v1-16b-a3b")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, experts_held=(0, 8), dispatch="grouped"))
    p = jax.eval_shape(
        lambda: L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32))

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    experts = {k: spec(p[k].shape) for k in ("up", "gate", "down")}
    assert experts["up"].shape == (8, 2048, 1408)
    txt = jax.jit(lambda p, x, w, i: L._grouped_experts(
        p, cfg, x, w, i)).lower(
        experts, spec((16, 512, 2048)), spec((16, 512, 6)),
        spec((16, 512, 6), jnp.int32)).compile().as_text()
    assert " conditional(" in txt
    rows = {int(r) for r in re.findall(r"= \w+\[(\d+),2048\]\S* gather\(",
                                       txt)}
    assert rows == {7680, 15360, 49152}
