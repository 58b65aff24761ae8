"""One transfer a launch: the step programs take a launch's host inputs as
one int32 buffer (``decoder.decode_buffer``, ``decoder.prefill_buffer``)
and merge a chaining lane's in-flight token inside the decode step (`llm`
marker, CPU tier-1, tiny models of each block the engine serves).

- the packed program, the documented separate-input form and the step
  compiled over the separate inputs return the same pools, tokens and
  logits to the bit, for the classic block and each recurrent kind;
- a chaining launch is the old merge program followed by the decode step;
- the benchmark's check of the engine's programs
  (``chipbench.serve.paged_logits``) drives the executables the engine
  runs and compiles no large program of its own.

No number here is a measurement of the chip."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import serve as bench_serve  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import decoder  # noqa: E402

pytestmark = pytest.mark.llm

B = 3                       # lanes of the decode batch; lane 2 idles


def classic():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=64), 8, 4


def state_space():
    return decoder.hybrid_lm(seed=3, dtype="float32"), 8, 4


def routed_delta():
    return decoder.routed_delta_lm(
        seed=1, dtype="float32", vocab_size=96, num_layers=4, units=32,
        num_heads=4, num_kv_heads=2, head_dim=16, attention_layers=[0],
        linear_attn={"num_heads": 4, "head_dim": 8,
                     "short_conv_kernel_size": 4, "num_kv_heads": None},
        experts_held=4, expert_shares=4, experts_per_token=3,
        expert_hidden=24, max_length=128), 8, 4


def retention():
    # one page a sequence: a retention entry is held once a sequence
    return decoder.retention_lm(
        seed=1, dtype="float32", vocab_size=96, num_layers=2, units=32,
        hidden_size=48, num_heads=4, num_kv_heads=2, head_dim=8,
        rope_theta=1e4, max_length=128), 32, 1


MODELS = {"classic": classic, "state_space": state_space,
          "routed_delta": routed_delta, "retention": retention}


@jax.jit
def combine(chained, staged, carry):
    """The merge the async engine launched as a program of its own."""
    return jnp.where(carry, chained, staged)


def host(tree):
    return [onp.asarray(a) for a in jax.tree.leaves(tree)]


def drive(lm, S, pps, route):
    """Two prompts prefilled chunk by chunk into lanes 0 and 1, two decode
    steps of both, then a launch in which lane 0 chains on the in-flight
    tokens and lane 1 is staged from the host.  ``route`` picks the call
    form: ``packed`` (the engine's: one host buffer a launch, the carried
    token merged inside the step), ``separate`` (the four documented
    inputs through the same executable, the merge done by ``combine``) or
    ``unpacked`` (the step compiled over the separate inputs, as it was
    before the buffer, after ``combine``).  Returns every output, on the
    host."""
    cfg, params, chunk = lm.config, lm.jax_params(), 8
    prefill = decoder.make_prefill_chunk(cfg, S, chunk)
    decode = decoder.make_decode_step(cfg, S)
    if route == "unpacked":
        prefill_fn = jax.jit(prefill.body, donate_argnums=(1, 2))
        decode_fn = jax.jit(decode.body, donate_argnums=(1, 2))
    else:
        prefill_fn, decode_fn = prefill, decode
    total = B * pps + 1
    kp, vp = (decoder.fresh_pool(cfg, total, S) for _ in range(2))
    tables = onp.zeros((B, pps), onp.int32)
    outs, pending = [], {}
    prompts = {0: list(range(3, 14)), 1: [5, 9, 2]}
    for lane, prompt in prompts.items():
        tables[lane] = onp.arange(total - 1 - lane * pps,
                                  total - 1 - (lane + 1) * pps, -1)
        for lo in range(0, len(prompt), chunk):
            part = prompt[lo:lo + chunk]
            padded = onp.zeros(chunk, onp.int32)
            padded[:len(part)] = part
            args = (padded, lo, len(part), tables[lane])
            if route == "packed":
                kp, vp, tok, last = prefill_fn(
                    params, kp, vp,
                    jnp.asarray(decoder.prefill_buffer(*args)))
            else:
                kp, vp, tok, last = prefill_fn(
                    params, kp, vp, *(jnp.asarray(a, jnp.int32)
                                      for a in args))
            outs += host((tok, last))
        pending[lane] = int(tok)
    pos = {lane: len(p) for lane, p in prompts.items()}
    active = onp.array([True, True, False])
    chained = None
    for step in range(3):
        staged = onp.zeros(B, onp.int32)
        positions = onp.zeros(B, onp.int32)
        carry = onp.zeros(B, bool)
        for lane in (0, 1):
            positions[lane] = pos[lane]
            staged[lane] = pending[lane]
        if step == 2:
            # lane 0 chains on the last launch; lane 1 is staged anew
            carry[0], staged[0], staged[1] = True, 0, 7
        if route == "packed":
            buf = decoder.decode_buffer(staged, positions, tables, active,
                                        carry)
            kp, vp, nxt, logits = decode_fn(
                params, kp, vp,
                chained if carry.any() else jnp.zeros(B, jnp.int32),
                jnp.asarray(buf))
        else:
            tokens = (combine(chained, jnp.asarray(staged), jnp.asarray(carry))
                      if carry.any() else jnp.asarray(staged))
            kp, vp, nxt, logits = decode_fn(
                params, kp, vp, tokens, jnp.asarray(positions),
                jnp.asarray(tables), jnp.asarray(active))
        outs += host((nxt, logits))
        chained = nxt
        for lane in (0, 1):
            pending[lane] = int(onp.asarray(nxt)[lane])
            pos[lane] += 1
    return outs + host((kp, vp))


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_packed_launch_is_the_same_computation(kind):
    lm, S, pps = MODELS[kind]()
    packed = drive(lm, S, pps, "packed")
    for route in ("separate", "unpacked"):
        other = drive(lm, S, pps, route)
        assert len(other) == len(packed)
        for a, b in zip(packed, other):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert onp.array_equal(a, b), (route, a, b)


def test_buffers_hold_the_inputs_where_the_programs_read_them():
    tables = onp.arange(6, dtype=onp.int32).reshape(2, 3)
    buf = decoder.decode_buffer(onp.array([4, 5]), onp.array([6, 7]),
                                tables, onp.array([True, False]),
                                onp.array([False, True]))
    assert buf.dtype == onp.int32
    assert buf.tolist() == [[4, 0, 6, 1, 0, 1, 2], [5, 1, 7, 0, 3, 4, 5]]
    assert decoder.decode_buffer([1], [2], tables[:1], [True]).tolist() == [
        [1, 0, 2, 1, 0, 1, 2]]
    buf = decoder.prefill_buffer(onp.array([9, 8, 0, 0]), 12, 2,
                                 onp.array([3, 1]))
    assert buf.dtype == onp.int32
    assert buf.tolist() == [9, 8, 0, 0, 12, 2, 3, 1]


def test_benchmark_check_drives_the_engines_executables():
    """``chipbench.serve.paged_logits`` calls the factories with the
    separate inputs: it gets the engine's own step programs from the
    cache, and every call runs the executable the engine's launches run.
    What it compiles beyond the engine is the two small packs."""
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=64)
    eng = serving.DecodeEngine(lm, name="llm", slots=4, page_size=8,
                               prefill_chunk=8, max_ctx=64)
    try:
        eng.warmup()
        assert len(eng.submit([1, 2, 3], max_new_tokens=5).result(
            timeout=120)["tokens"]) == 5
    finally:
        assert eng.stop()
    compiles = decoder.fn_cache_stats()["compiles"]
    programs = {"decode": eng._decode_fn, "prefill": eng._prefill_fn}
    executables = {k: p.program._cache_size() for k, p in programs.items()}
    calls = dict.fromkeys(programs, 0)
    traced = []

    def listen(event, _secs, fun_name=None, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            traced.append(fun_name)
    jax.monitoring.register_event_duration_secs_listener(listen)

    def recording(key, program):
        def run(*args):
            calls[key] += 1
            return program(*args)
        return run
    saved = {k: p.program for k, p in programs.items()}
    try:
        for k, p in programs.items():
            p.program = recording(k, saved[k])
        fed, rows = bench_serve.paged_logits(eng, list(range(1, 12)), 3)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        for k, p in programs.items():
            p.program = saved[k]
    assert decoder.fn_cache_stats()["compiles"] == compiles
    assert calls == {"decode": 3, "prefill": 2}
    assert {k: p.program._cache_size()
            for k, p in programs.items()} == executables
    assert "step" not in traced and "prefill" not in traced
    assert len(fed) == 11 + 3 and rows.shape == (4, 64)
    assert onp.isfinite(rows).all()
