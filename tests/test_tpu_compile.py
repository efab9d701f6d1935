"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at qwen2-0.5b widths (what interpret mode cannot show:
tiling, VMEM and lowering rules), and the serving decode step, whose
optimized program must not copy the cache; plus the CPU interpret-mode
check of the fused state fingerprint under the engine's replica vmap.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs these tests loads the TPU compiler library."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.fingerprint as kfp
from repro.abft.kernels import abft_flash_attention, matmul_pallas
from repro.configs import (RunConfig, SedarConfig, TrainConfig,
                           get_config)
from repro.core.fingerprint import pytree_fingerprint_fused
from repro.kernels.fingerprint import fingerprint_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.runtime.serve import SedarServer

VOCAB, D, DFF, H, KV, HD = 151_936, 896, 4_864, 14, 2, 64
SLOTS = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _layer_state(shape_fn):
    """One qwen2-0.5b decoder layer's params plus the embedding and final
    norm — every layout the fused fingerprint meets in a model state."""
    return {"embed": shape_fn((VOCAB, D), jnp.float32),
            "wq": shape_fn((D, H * HD), jnp.float32),
            "wkv": shape_fn((D, 2 * KV * HD), jnp.float32),
            "bq": shape_fn((H * HD,), jnp.float32),
            "w_up": shape_fn((D, DFF), jnp.bfloat16),
            "norm": shape_fn((D,), jnp.float32),
            "step": shape_fn((), jnp.int32)}


@pytest.mark.parametrize("shape,dtype", [
    ((SLOTS, VOCAB), jnp.float32),        # one decode tick's logits
    ((1 << 20,), jnp.uint32),             # a packed state buffer
])
@pytest.mark.parametrize("replicas", [0, 2])
def test_fingerprint_kernel_compiles(one_chip, shape, dtype, replicas):
    fn = lambda x: fingerprint_pallas(x, interpret=False)
    if replicas:
        fn, shape = jax.vmap(fn), (replicas,) + shape
    txt = _compile_text(fn, jax.ShapeDtypeStruct(shape, dtype,
                                                 sharding=one_chip))
    assert "tpu_custom_call" in txt


def test_fused_state_fingerprint_compiles_vmapped(one_chip, monkeypatch):
    """The fused executor's validate program: the whole-state fingerprint
    vmapped over two stacked replicas, hashed leaf by leaf in place."""
    monkeypatch.setattr(kfp, "default_interpret", lambda: False)
    state = _layer_state(lambda s, dt: jax.ShapeDtypeStruct(
        (2,) + s, dt, sharding=one_chip))
    txt = _compile_text(jax.vmap(pytree_fingerprint_fused), state)
    assert txt.count("tpu_custom_call") >= len(state)


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, H, 256, HD), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, KV, 256, HD), jnp.bfloat16,
                              sharding=one_chip)
    txt = _compile_text(lambda q, k, v: flash_attention_pallas(
        q, k, v, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in txt


def test_abft_kernels_compile(one_chip):
    """The checksummed kernels at qwen2-0.5b widths: the MLP up-projection
    of a 128-row block with its checksum row and column, and attention
    with the checksum lane on V."""
    a = jax.ShapeDtypeStruct((129, D), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((D, DFF + 1), jnp.float32, sharding=one_chip)
    txt = _compile_text(lambda a, b: matmul_pallas(a, b, interpret=False),
                        a, b)
    assert "tpu_custom_call" in txt
    q = jax.ShapeDtypeStruct((1, H, 256, HD), jnp.float32, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, KV, 256, HD), jnp.float32,
                              sharding=one_chip)
    txt = _compile_text(lambda q, k, v: abft_flash_attention(
        q, k, v, interpret=False)[0], q, kv, kv)
    assert "tpu_custom_call" in txt


def _copies(txt: str):
    """(element count, line) of every copy in optimized HLO text."""
    out = []
    for line in txt.splitlines():
        m = re.search(r"= \(?\w+\[([\d,]*)\]\S* copy(-start)?\(", line)
        if m:
            out.append((math.prod(int(d) for d in m.group(1).split(",")
                                  if d), line.strip()[:160]))
    return out


@pytest.mark.parametrize("backend", ["none", "fused"])
def test_packed_decode_copies_no_whole_cache_leaf(one_chip, backend):
    """The serving decode step (`jit_sedar_decode_step`, and under fused
    `jit_sedar_fused_decode_step` with its per-slot commit gate) of
    qwen2-0.5b as the benchmark serves it, 16 slots of 2,312 positions (a
    cache too large to stage in VMEM whole): the layer loop writes each
    slot's row into the layer-major cache in place and reads it as it lies,
    so no copy in the optimized program is as large as one replica's cache
    leaf (a relayout of the cache into or out of the loop, or a second
    buffer for the gate's pre-step state)."""
    rc = RunConfig(model=get_config("qwen2-0.5b"), train=TrainConfig(),
                   sedar=SedarConfig(validate_lag=8))
    srv = SedarServer(rc, backend=backend)
    slots, T = 16, 2312
    eng = srv._batch_engine(slots, T, 8)[0]
    on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, srv.model.abstract_params()[0])
    state = jax.eval_shape(lambda: srv.packed_state(slots, T))
    leaf = max(a.size for a in jax.tree.leaves(state["cache"]))
    flag = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip)
    if backend == "fused":
        stacked = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            (2,) + a.shape, a.dtype, sharding=one_chip), state)
        lowered = eng.executor._step_gated.lower(stacked, params, flag, flag)
    else:
        rid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        lowered = eng.executor.step_fn.lower(
            jax.tree.map(on_chip, state), params, rid, flag)
    big = [line for n, line in _copies(lowered.compile().as_text())
           if n >= leaf]
    assert not big, big


def test_fused_fingerprint_pallas_vmapped_matches_jnp():
    """CPU interpret mode: the Pallas branch of the fused fingerprint under
    the replica vmap gives the jnp branch's hash words, and a one-bit flip
    in replica 1 shows as a mismatch there only."""
    rs = np.random.RandomState(0)
    tree = {"a": rs.randn(5).astype(np.float32),
            "b": rs.randn(40, 128).astype(np.float32),
            "c": rs.randn(3, 16, 384).astype(np.float32),
            "d": rs.randn(37, 2688).astype(np.float32),
            "e": rs.randn(300, 128).astype(jnp.bfloat16),
            "f": rs.randn(5, 7, 64).astype(np.float32),
            "g": np.arange(7, dtype=np.int32)}
    stacked = jax.tree.map(lambda l: jnp.stack([jnp.asarray(l)] * 2), tree)
    pallas = jax.jit(jax.vmap(
        lambda t: pytree_fingerprint_fused(t, use_pallas=True)))
    plain = jax.jit(jax.vmap(
        lambda t: pytree_fingerprint_fused(t, use_pallas=False)))
    clean = np.asarray(pallas(stacked))
    np.testing.assert_array_equal(clean[:, :2], np.asarray(plain(stacked))[:, :2])
    np.testing.assert_array_equal(clean[0, :2], clean[1, :2])
    for leaf, idx in (("c", (1, 2, 15, 383)), ("d", (1, 36, 2687)),
                      ("e", (1, 299, 0))):
        bits = jax.lax.bitcast_convert_type(
            stacked[leaf][idx],
            jnp.uint16 if stacked[leaf].dtype == jnp.bfloat16 else jnp.uint32)
        flipped = jax.lax.bitcast_convert_type(bits ^ 1, stacked[leaf].dtype)
        fps = np.asarray(pallas({**stacked, leaf: stacked[leaf].at[idx].set(
            flipped)}))
        np.testing.assert_array_equal(fps[0, :2], clean[0, :2])
        assert not np.array_equal(fps[1, :2], clean[1, :2]), leaf
        np.testing.assert_array_equal(
            fps[:, :2], np.asarray(plain({**stacked, leaf: stacked[leaf]
                                          .at[idx].set(flipped)}))[:, :2])
