"""SEDAR comparison hot-spot: fingerprint throughput.

Three measurements:
  * single-tensor jnp reduction (baseline GB/s),
  * per-leaf vs FUSED whole-state fingerprint on a many-leaf model-like
    state — the fused path hashes every leaf in place at its global offset
    and returns one fingerprint for the whole state, which is the engine's
    hot validation path,
  * Pallas kernel correctness vs the jnp oracle (interpret mode on CPU —
    relative numbers only; the BlockSpec tiling is what a TPU executes).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.fingerprint import (pytree_fingerprint,
                                    pytree_fingerprint_fused,
                                    tensor_fingerprint)
from repro.kernels import ops

SIZES = [1 << 16, 1 << 20]
MIN_STATE_LEAVES = 32      # acceptance: fused must win on a >=32-leaf state


def _recurrent_like_state(n_layers: int = 32, d: int = 32, seed: int = 0):
    """Recurrent/ssm-shaped params+AdamW state: many layers of small gate
    mats, vectors and scales, each with optimizer m/v copies (the xLSTM /
    recurrentgemma leaf census). Leaf-count-bound: the regime the fused
    whole-state path targets — on accelerators each leaf is otherwise its
    own kernel launch."""
    rs = np.random.RandomState(seed)
    tree = {}

    def add(name, shape):
        for copy in ("p", "m", "v"):
            tree[f"{name}.{copy}"] = jnp.asarray(
                rs.randn(*shape).astype(np.float32))

    for i in range(n_layers):
        add(f"l{i:02d}.w_gate", (d, d))
        add(f"l{i:02d}.b_gate", (d,))
        add(f"l{i:02d}.ln", (d,))
    return tree


def _transformer_like_state(n_layers: int = 8, d: int = 64, seed: int = 0):
    """Transformer-shaped state: bytes dominated by a few big mats + embed
    (bandwidth-bound regime; per-leaf XLA reductions are already near-optimal
    on CPU here — the fused win in this regime is the single kernel launch
    on real accelerators)."""
    rs = np.random.RandomState(seed)
    tree = {}

    def add(name, shape):
        for copy in ("p", "m", "v"):
            tree[f"{name}.{copy}"] = jnp.asarray(
                rs.randn(*shape).astype(np.float32))

    add("embed", (2048, d))
    for i in range(n_layers):
        add(f"l{i:02d}.wqkv", (d, 3 * d))
        add(f"l{i:02d}.wo", (d, d))
        add(f"l{i:02d}.w1", (d, 4 * d))
        add(f"l{i:02d}.w2", (4 * d, d))
        add(f"l{i:02d}.ln1", (d,))
        add(f"l{i:02d}.ln2", (d,))
    return tree


def main() -> None:
    for n in SIZES:
        x = jnp.asarray(np.random.RandomState(0).randn(n).astype(np.float32))
        jnp_fn = jax.jit(tensor_fingerprint)
        jax.block_until_ready(jnp_fn(x))
        us = timeit(lambda: jax.block_until_ready(jnp_fn(x)), iters=5)
        gbps = n * 4 / (us * 1e-6) / 1e9
        emit(f"fingerprint_jnp_{n}", us, f"GB/s={gbps:.2f}")

    # fused whole-state vs per-leaf on many-leaf states (the engine's
    # validation boundary), in both leaf-census regimes. Interleaved min-of-N
    # timing: the two paths alternate within each iteration so background
    # load hits both equally (sequential medians drift on shared CPUs).
    per_leaf = jax.jit(pytree_fingerprint)
    fused = jax.jit(lambda t: pytree_fingerprint_fused(t, use_pallas=False))

    def interleaved_min_us(state, iters=25):
        jax.block_until_ready(per_leaf(state))
        jax.block_until_ready(fused(state))
        tl, tf = [], []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(per_leaf(state))
            tl.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fused(state))
            tf.append(time.perf_counter() - t0)
        return min(tl) * 1e6, min(tf) * 1e6

    for label, state in (("recurrent", _recurrent_like_state()),
                         ("transformer", _transformer_like_state())):
        n_leaves = len(jax.tree.leaves(state))
        assert n_leaves >= MIN_STATE_LEAVES
        us_leaf, us_fused = interleaved_min_us(state)
        nbytes = sum(l.size * 4 for l in jax.tree.leaves(state))
        emit(f"fingerprint_per_leaf_{label}_{n_leaves}leaves", us_leaf,
             f"GB/s={nbytes / (us_leaf * 1e-6) / 1e9:.2f}")
        emit(f"fingerprint_fused_{label}_{n_leaves}leaves", us_fused,
             f"GB/s={nbytes / (us_fused * 1e-6) / 1e9:.2f}")
        emit(f"fingerprint_fused_speedup_{label}_{n_leaves}leaves", 0.0,
             f"x{us_leaf / max(us_fused, 1e-9):.2f}_fused_beats_per_leaf="
             f"{bool(us_fused < us_leaf)}")

    # kernel correctness + parity with the packed jnp path
    x = jnp.asarray(np.random.RandomState(0).randn(1 << 14).astype(np.float32))
    a = np.asarray(ops.fingerprint(x))
    from repro.kernels.ref import fingerprint_ref
    b = np.asarray(fingerprint_ref(x))
    emit("fingerprint_pallas_vs_oracle", 0.0,
         f"hash_exact_match={bool(np.array_equal(a[:2], b[:2]))}")
    state = _transformer_like_state()
    c = np.asarray(pytree_fingerprint_fused(state, use_pallas=True))
    d = np.asarray(pytree_fingerprint_fused(state, use_pallas=False))
    emit("fingerprint_pallas_fused_vs_fused_jnp", 0.0,
         f"hash_exact_match={bool(np.array_equal(c[:2], d[:2]))}")


if __name__ == "__main__":
    main()
