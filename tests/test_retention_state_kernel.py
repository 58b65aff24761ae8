"""The retention decode step's state kernel (`ops/pallas/retention_state.py`)
in the Pallas interpreter against the XLA form it replaces on the chip
(`models/hybrid.py:_retention_pool_xla`): one layer's update over pools of
random entries, lanes active, idle on the scratch page, fresh at position 0
and on pages that are not adjacent; then the whole decode step of the tiny
retention model through either form.

Tiny sizes on the CPU (heads of 8: 36 features).  No number here is a
measurement of the chip."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import decoder, hybrid
from mxnet_tpu.ops.pallas import retention_state as rs

pytestmark = pytest.mark.llm

#: lanes: page, active, fresh (page 0 is the scratch page)
LANES = [(3, True, False), (0, False, False), (7, True, True),
         (1, True, False), (5, True, False), (0, False, False)]
P, L, LI = 9, 3, 1

#: (KV heads, head_dim, query heads a KV head, z parts, largest block):
#: one KV head a pool and z whole; two, z in two parts, blocks of a head's
#: 36 rows; blocks of 24 rows, so that one block holds the end of a head
#: and the start of the next and a lane takes more blocks than the ring
CASES = {
    "one_head_a_pool": (2, 8, 2, 1, 768),
    "two_heads_z_in_parts": (4, 8, 5, 2, 768),
    "blocks_across_heads": (4, 8, 5, 2, 24),
}


def pools(rng, KVH, d, parts):
    F = hybrid.phi_width(d)
    out = []
    for _ in range(2):
        conv = tuple(jnp.asarray(rng.standard_normal(
            (L, P, KVH // 2 * F // parts)), jnp.float32)
            for _ in range(parts))
        out.append(hybrid.HybridPool(
            rows=jnp.zeros((0, P, 4, KVH * d), jnp.float32),
            ssm=tuple(jnp.asarray(rng.standard_normal(
                (P, KVH // 2, F, d)), jnp.float32) for _ in range(L)),
            conv=conv if parts > 1 else conv[0]))
    return out


def parts_of(pool):
    return pool.conv if isinstance(pool.conv, tuple) else (pool.conv,)


@pytest.mark.parametrize("KVH, d, G, parts, largest", list(CASES.values()),
                         ids=list(CASES))
def test_the_kernel_is_the_xla_form_and_touches_no_other_page(
        monkeypatch, KVH, d, G, parts, largest):
    monkeypatch.setattr(rs, "_MAX_BLOCK", largest)
    rng = np.random.default_rng(KVH * d + largest)
    kp, vp = pools(rng, KVH, d, parts)
    F = hybrid.phi_width(d)
    B = len(LANES)
    pages, active, fresh = (jnp.asarray(c) for c in zip(*LANES))
    pages = pages.astype(jnp.int32)
    g = jnp.where(fresh[:, None], 0.0, jnp.asarray(rng.uniform(
        0.5, 1.0, (B, KVH)), jnp.float32))
    pq, pk, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
                 for s in ((B, KVH, G, F), (B, KVH, F), (B, KVH, d)))
    num0, den0, want = hybrid._retention_pool_xla(kp, vp, LI, pages, g, pq,
                                                  pk, v, KVH)
    rs.last_path = None
    num, den, S, z = rs.decode_state_step(
        "interpret", LI, pages, active, fresh, g, pq, pk, v,
        (kp.ssm[LI], vp.ssm[LI]), (parts_of(kp), parts_of(vp)))
    assert rs.last_path == "pallas-interpret"
    # what a lane reads: the state before the write (a fresh lane's is
    # zero: it starts from nothing), zeros for an idle lane
    read = np.asarray(active & ~fresh)
    np.testing.assert_allclose(np.asarray(num)[read],
                               np.asarray(num0)[read], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(den)[read],
                               np.asarray(den0)[read], rtol=1e-5, atol=1e-5)
    for got in (num, den):
        assert not np.asarray(got)[~read].any()
    owned = np.asarray(pages)[np.asarray(active)]
    other = np.setdiff1d(np.arange(P), owned)
    assert 0 in other
    for pool, new, S_new, z_new in zip((kp, vp), want, S, z):
        # the owned entries as the XLA form writes them
        np.testing.assert_allclose(np.asarray(S_new)[owned],
                                   np.asarray(new.ssm[LI])[owned],
                                   rtol=1e-5, atol=1e-5)
        z_new = np.concatenate([np.asarray(a) for a in z_new], -1)
        z_want = np.concatenate([np.asarray(a) for a in parts_of(new)], -1)
        np.testing.assert_allclose(z_new[LI, owned], z_want[LI, owned],
                                   rtol=1e-5, atol=1e-5)
        # every other page, the scratch page among them, to the bit; and
        # the owned pages' z of the other layers
        z_old = np.concatenate([np.asarray(a) for a in parts_of(pool)], -1)
        assert np.asarray(S_new)[other].tobytes() == np.asarray(
            pool.ssm[LI])[other].tobytes()
        assert z_new[:, other].tobytes() == z_old[:, other].tobytes()
        rest = [i for i in range(L) if i != LI]
        assert z_new[rest].tobytes() == z_old[rest].tobytes()


def test_the_decode_step_through_the_kernel_is_the_xla_forms(monkeypatch):
    """The tiny retention model's decode step, both forms from the same
    pools: logits to float32 rounding, the greedy tokens the same, every
    page no active lane owns unchanged to the bit."""
    lm = decoder.retention_lm(seed=2, dtype="float32", vocab_size=64,
                              num_layers=2, units=32, hidden_size=48,
                              num_heads=8, num_kv_heads=4, head_dim=8,
                              rope_theta=1e4, max_length=32)
    cfg, params = lm.config, lm.jax_params()
    rng = np.random.default_rng(5)
    B, S = 5, 32
    base = [jax.tree.map(lambda a: jnp.asarray(
        rng.uniform(0, 0.1, a.shape), a.dtype),
        decoder.fresh_pool(cfg, 8, S)) for _ in range(2)]
    tables = jnp.asarray([[2], [0], [6], [3], [0]], jnp.int32)
    rest = (jnp.asarray(rng.integers(0, 64, B), jnp.int32),
            jnp.asarray([4, 0, 0, 9, 0], jnp.int32), tables,
            jnp.asarray([True, False, True, True, False]))
    outs = {}
    for mode in ("interpret", None):
        monkeypatch.setattr(rs, "kernel_mode", lambda S, z, mode=mode: mode)
        outs[mode] = hybrid.build_decode_step(cfg, S)(
            params, *jax.tree.map(jnp.copy, base), *rest)
        assert hybrid.retention_decode_path() == (
            "pallas-interpret" if mode else "xla")
    new, old = outs["interpret"], outs[None]
    live = np.asarray(rest[3])
    np.testing.assert_allclose(np.asarray(new[3])[live],
                               np.asarray(old[3])[live], rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(new[2])[live], np.asarray(old[2])[live])
    owned = np.asarray(tables[:, 0])[live]
    other = np.setdiff1d(np.arange(8), owned)
    for before, after, xla in zip(base, new[:2], old[:2]):
        for a, b, c in zip(jax.tree.leaves(before)[1:],
                           jax.tree.leaves(after)[1:],
                           jax.tree.leaves(xla)[1:]):
            axis = 1 if a.ndim == 3 else 0          # z (L, P, W); S (P, ..)
            np.testing.assert_allclose(np.take(np.asarray(b), owned, axis),
                                       np.take(np.asarray(c), owned, axis),
                                       rtol=1e-5, atol=1e-5)
            # the XLA form writes the idle lanes' scratch page; the kernel
            # leaves it as it was
            assert np.take(np.asarray(b), other, axis).tobytes() == np.take(
                np.asarray(a), other, axis).tobytes()


def test_blocks_fill_whole_tiles_at_the_published_widths():
    """8 KV heads of 128: a pool half's 33 024 rows in 43 blocks of 768
    (whole lane tiles of the query rows and of z, at most two heads a
    block); the chip's kernel is selected by what the state is."""
    F = hybrid.phi_width(128)
    assert rs.block_rows(4 * F, F) == 768 and 4 * F // 768 == 43
    S = jax.ShapeDtypeStruct((21, 4, F, 128), jnp.float32)
    z = (jax.ShapeDtypeStruct((4, 21, 2 * F), jnp.float32),) * 2
    assert rs.kernel_mode(S, z) is None                 # the CPU
    with pytest.MonkeyPatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        assert rs.kernel_mode(S, z) == "compiled"
        tiny = jax.ShapeDtypeStruct((9, 1, 36, 8), jnp.float32)
        assert rs.kernel_mode(tiny, (jax.ShapeDtypeStruct(
            (2, 9, 36), jnp.float32),)) is None
        assert rs.kernel_mode(jax.ShapeDtypeStruct(S.shape, jnp.bfloat16),
                              z) is None
