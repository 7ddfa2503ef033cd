"""Compile the main path's Pallas kernels for a TPU v5e described without
a chip, at yi-9b head geometry (32 query / 4 KV heads, head_dim 128, bf16,
128-token tiles), and check that the program holds the Mosaic kernel
(``tpu_custom_call``), not the interpreter.

Interpret mode checks none of Mosaic's layout rules; this file is where a
block shape the TPU compiler refuses fails, on the CPU. The topology is
described inside a module-scoped fixture (never at import): only one
process may load the TPU library, and under several test workers only the
worker running this file does so.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tri_3body import ops as O3
from repro.kernels.tri_attn import ops as OPS
from repro.kernels.tri_edm import ops as OE
from repro.serve import decode as D

H, HKV, HD, BLK = 32, 4, 128, 128
SLOTS, S_CACHE = 8, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_for_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _packed():
    return OPS.make_packed_sched([2 * BLK, 5 * BLK, 3 * BLK], block=BLK)


def _qkv(s):
    bf = jnp.bfloat16
    return ((1, H, s, HD), bf), ((1, HKV, s, HD), bf), ((1, HKV, s, HD), bf)


def _decode_inputs():
    bf = jnp.bfloat16
    return (((SLOTS, H, HD), bf), ((SLOTS, S_CACHE, HKV, HD), bf),
            ((SLOTS, S_CACHE, HKV, HD), bf))


KV_LENS, LIVE = [1, 130, 700, S_CACHE], [0, 3, 4, 6]


def packed_prefill_fwd():
    ps = _packed()
    return (lambda q, k, v: OPS.packed_prefill_attention(
        q, k, v, ps, impl="pallas")), _qkv(ps.s_total)


def packed_prefill_grad():
    ps = _packed()

    def grad(q, k, v):
        return jax.grad(lambda *a: jnp.sum(OPS.packed_prefill_attention(
            *a, ps, impl="pallas").astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)
    return grad, _qkv(ps.s_total)


def packed_decode():
    tbl, needed = OPS.make_decode_table(KV_LENS, LIVE, blk=BLK,
                                        n_members=SLOTS + 1, n_slots=SLOTS,
                                        s_cache=S_CACHE)
    spec = OPS.DecodeRoundSpec(n_members=SLOTS + 1,
                               capacity=D.round_capacity(needed), blk=BLK,
                               impl="pallas")
    return ((lambda q, k, v, t: OPS.packed_decode_attention(q, k, v, t,
                                                            spec)),
            _decode_inputs() + ((tbl.shape, jnp.int32),))


def fused_step():
    ps = OPS.make_packed_sched([2 * BLK, 3 * BLK], block=BLK)
    n_members = len(ps.members) + SLOTS + 1
    tbl, needed = OPS.make_fused_table(ps, KV_LENS, LIVE, blk=BLK,
                                       n_members=n_members, n_slots=SLOTS,
                                       s_cache=S_CACHE)
    spec = OPS.FusedStepSpec(
        n_members=n_members,
        capacity=ps.steps + D.round_capacity(needed - ps.steps), blk=BLK,
        impl="pallas")
    return ((lambda qp, kp, vp, qd, kc, vc, t: OPS.fused_step_attention(
        qp, kp, vp, qd, kc, vc, t, ps, spec)),
        _qkv(ps.s_total) + _decode_inputs() + ((tbl.shape, jnp.int32),))


def ltm_fwd():
    return ((lambda q, k, v: OPS.triangular_attention(
        q, k, v, impl="pallas", block_q=BLK, block_k=BLK)),
        _qkv(8 * BLK))


def tri_edm_ltm():
    return ((lambda x: OE.edm(x, BLK, impl="pallas")),
            (((32 * BLK, 4), jnp.float32),))


def tri_3body_strict():
    return ((lambda x: O3.three_body(x, BLK, impl="pallas", strict=True)),
            (((8 * BLK, 8), jnp.float32),))


@pytest.mark.parametrize("case", [
    packed_prefill_fwd, packed_prefill_grad, packed_decode, fused_step,
    ltm_fwd, tri_edm_ltm, tri_3body_strict], ids=lambda c: c.__name__)
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = case()
    hlo = _compile_for_chip(fn, one_chip, *shapes)
    assert "tpu_custom_call" in hlo
