"""CPU tests for the rules PR 21 set for running on the chip: nothing
hides the device, one process per chip, a compile cache placed from
outside, a native runtime built from what git tracks."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _native, runtime
from mxnet_tpu.models import decoder
from mxnet_tpu.ops.pallas import fused_cell
from mxnet_tpu.ops.pallas import paged_attention as paged

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(argv, env=None):
    full = dict(os.environ)
    full["PYTHONPATH"] = REPO + os.pathsep + full.get("PYTHONPATH", "")
    full.update(env or {})
    for k in [k for k, v in full.items() if v is None]:
        del full[k]
    return subprocess.Popen([sys.executable] + argv, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=full,
                            cwd=REPO)


def _run(argv, env=None, timeout=300):
    proc = _spawn(argv, env)
    out, err = proc.communicate(timeout=timeout)
    return subprocess.CompletedProcess(argv, proc.returncode, out, err)


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------
# runtime.py loaded by path: the function under test needs jax alone, and
# a child that imports the whole package costs three seconds more
_CACHE_SCRIPT = r"""
import importlib.util, json, sys, jax
spec = importlib.util.spec_from_file_location("runtime", sys.argv[1])
runtime = importlib.util.module_from_spec(spec)
spec.loader.exec_module(runtime)
before = jax.config.jax_compilation_cache_dir
got = runtime.enable_compile_cache()
print(json.dumps({"before": before, "got": got,
                  "after": jax.config.jax_compilation_cache_dir,
                  "again": runtime.enable_compile_cache(),
                  "floor_s":
                  jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


def _cache_probes(env, n=1):
    """``n`` concurrent children, each asked where its cache is."""
    procs = [_spawn(["-c", _CACHE_SCRIPT, runtime.__file__], env)
             for _ in range(n)]
    docs = []
    for proc in procs:
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err[-2000:]
        docs.append(json.loads(out.strip().splitlines()[-1]))
    return docs


def test_compile_cache_follows_the_jax_variable(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and
    the repo's code sets nothing."""
    where = str(tmp_path / "cache")
    doc, = _cache_probes({"JAX_COMPILATION_CACHE_DIR": where})
    assert doc == {"before": where, "got": where, "after": where,
                   "again": where, "floor_s": 1.0}     # JAX's own default


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    docs = _cache_probes({"JAX_COMPILATION_CACHE_DIR": None}, n=2)
    assert docs[0] == docs[1]
    assert docs[0]["before"] is None
    assert docs[0]["got"] == docs[0]["after"] == runtime.COMPILE_CACHE_DIR
    assert docs[0]["floor_s"] == 0      # small serving programs persist
    assert runtime.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                             cwd=REPO)
    if os.path.isdir(os.path.join(REPO, ".git")):
        assert ignored.returncode == 0


def test_import_leaves_the_compile_cache_off():
    assert runtime.compile_cache_stats()["dir"] is None


# ---------------------------------------------------------------------------
# chip_smoke.py needs the chip
# ---------------------------------------------------------------------------
def test_chip_smoke_refuses_the_cpu():
    out = _run(["chip_smoke.py"], env={"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith("CHIP_SMOKE FAILED") and "no TPU" in last
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, timeout=120,
                         cwd=str(tmp_path),
                         env={k: v for k, v in os.environ.items()
                              if k != "PYTHONPATH"})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


# ---------------------------------------------------------------------------
# no path hides the device
# ---------------------------------------------------------------------------
def test_tpu_context_beyond_the_device_count_raises():
    n = len(jax.devices())
    assert mx.tpu(n - 1).jax_device == jax.devices()[n - 1]
    with pytest.raises(ValueError, match="has %d device" % n):
        mx.tpu(n).jax_device
    with pytest.raises(ValueError):
        mx.gpu(-1).jax_device


def test_paged_attention_interpret_runs_the_kernel(monkeypatch):
    """MXNET_PAGED_ATTENTION=interpret interprets jax's TPU kernel, in
    the decode step's position (inside a jit), and agrees with the
    gather reference to the kernel's own rounding."""
    monkeypatch.setenv("MXNET_PAGED_ATTENTION", "interpret")
    rs = onp.random.RandomState(0)
    B, H, KVH, D, S, pps = 3, 4, 2, 64, 16, 4
    P = B * pps + 1
    q = jnp.asarray(rs.randn(B, H, D).astype("float32"))
    kp = jnp.asarray(rs.randn(KVH, P, S, D).astype("float32"))
    vp = jnp.asarray(rs.randn(KVH, P, S, D).astype("float32"))
    lengths = jnp.asarray([17, 40, 64], jnp.int32)
    tables = jnp.asarray(onp.arange(1, P).reshape(B, pps), jnp.int32)
    paged.last_path = None
    out = jax.jit(paged.paged_attention)(q, kp, vp, lengths, tables)
    assert paged.last_path == "pallas-interpret"
    ref = paged.paged_attention_reference(q, kp, vp, lengths, tables)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)


def test_kernel_gates_select_by_backend_alone(monkeypatch):
    """No probe: on a TPU backend every gate answers "compiled"."""
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.pallas import epilogue, quant_matmul
    for var in ("MXNET_FLASH_ATTENTION", "MXNET_EPILOGUE_KERNEL",
                "MXNET_PAGED_ATTENTION", "MXNET_QUANT_MATMUL",
                "MXNET_RNN_FUSED_CELL"):
        monkeypatch.delenv(var, raising=False)
    gates = (attention._pallas_mode, epilogue._mode, paged._mode,
             quant_matmul.quant_mode, fused_cell.rnn_mode)
    assert [g() for g in gates] == [None] * len(gates)   # CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [g() for g in gates] == ["compiled"] * len(gates)


def test_compiled_lane_takes_a_kernel_only_where_it_compiles(monkeypatch):
    """What the v5e's compiler refused in PR 21 is selected away by what
    the code can see: paged attention needs head_dim on the 128 lanes,
    the int4 dequant-matmul is not taken.  (The compiled calls themselves
    cannot run here; on the CPU they raise, which shows they were
    selected.)"""
    from mxnet_tpu.ops.pallas import quant_matmul as qmm
    monkeypatch.delenv("MXNET_PAGED_ATTENTION", raising=False)
    monkeypatch.delenv("MXNET_QUANT_MATMUL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rs = onp.random.RandomState(0)

    def attend(d):
        q = jnp.asarray(rs.randn(2, 2, d).astype("float32"))
        kv = jnp.asarray(rs.randn(2, 5, 8, d).astype("float32"))
        paged.paged_attention(q, kv, kv, jnp.asarray([3, 9], jnp.int32),
                              jnp.asarray([[1, 2], [3, 4]], jnp.int32))

    attend(64)
    assert paged.last_path == "xla"
    with pytest.raises(Exception):
        attend(128)
    w = rs.randn(16, 32).astype("float32")
    x = jnp.ones((2, 32), jnp.float32)
    qmm.quant_matmul(x, qmm.quantize_w4(w))
    assert qmm.last_path == "xla"
    with pytest.raises(Exception):
        qmm.quant_matmul(x, qmm.quantize_w8(w))


def test_epilogue_kernel_is_not_handed_to_gspmd(monkeypatch):
    """A Mosaic kernel cannot be partitioned automatically: under an
    active multi-device ShardingConfig the compiled lane takes the jnp
    chain, except inside a manual (shard_map) region."""
    from mxnet_tpu.ops.pallas import epilogue
    from mxnet_tpu.parallel import ShardingConfig, manual_lowering
    monkeypatch.delenv("MXNET_EPILOGUE_KERNEL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert epilogue._mode() == "compiled"
    with ShardingConfig(mesh_shape=(1,), axis_names=("dp",)).scope():
        assert epilogue._mode() == "compiled"       # one device: no GSPMD
    with ShardingConfig(mesh_shape=(4,), axis_names=("dp",)).scope():
        assert epilogue._mode() is None
        with manual_lowering():
            assert epilogue._mode() == "compiled"


def test_engine_names_the_decode_program_it_runs():
    from mxnet_tpu.serving import DecodeEngine
    lm = decoder.decoder_tiny_lm(seed=0)
    eng = DecodeEngine(lm, slots=2, page_size=8, max_ctx=32)
    try:
        # one decode program, the builders' own, and its census
        assert eng._decode_fn is decoder.make_decode_step(lm.config, 8)
        assert eng.stats()["launches"] == decoder.decode_launch_stats(
            lm.jax_params(), lm.config, 8, 2, eng.pages_per_seq,
            eng.alloc.total_pages)
        assert not hasattr(eng, "_run_decode_fn")
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------
def test_supervisor_shows_each_replica_one_chip(monkeypatch):
    from mxnet_tpu import context
    from mxnet_tpu.serving.supervisor import ReplicaSupervisor
    sup = ReplicaSupervisor({"models": []}, replicas=2, ports=[1, 2])
    r0, r1 = sup.replicas
    assert sup._chip_env(r0, {}) == {}          # this host has no TPU
    monkeypatch.setattr(context, "host_chip_count", lambda: 2)
    assert sup._chip_env(r0, {"JAX_PLATFORMS": "cpu"}) == {}
    assert sup._chip_env(r0, {"TPU_VISIBLE_CHIPS": "1"}) == {}  # theirs wins
    e0, e1 = sup._chip_env(r0, {}), sup._chip_env(r1, {})
    assert (e0["TPU_VISIBLE_CHIPS"], e1["TPU_VISIBLE_CHIPS"]) == ("0", "1")
    assert e0["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert e0["TPU_PROCESS_PORT"] != e1["TPU_PROCESS_PORT"]
    assert sup._chip_env(r0, {}) == e0          # a restart keeps its chip
    other = ReplicaSupervisor({"models": []}, replicas=1, ports=[3])
    assert (other._chip_env(other.replicas[0], {})["TPU_PROCESS_PORT"]
            != e0["TPU_PROCESS_PORT"])          # two fleets do not meet
    # a third replica on a two-chip host is refused where it is asked for
    sup.env["JAX_PLATFORMS"] = ""
    sup._spec_path = "unused"
    with pytest.raises(RuntimeError, match="has 2 TPU chip"):
        sup.add_replica()
    assert [r.rid for r in sup.replicas] == ["r0", "r1"]
    sup.stop_replica(r0.rid)                    # a retired one frees it
    r3 = sup.add_replica(spawn=False)
    assert sup._chip_env(r3, {})["TPU_VISIBLE_CHIPS"] == "0"
    with pytest.raises(RuntimeError, match="2-chip mesh"):
        sup._chip_env(r1, {"MXNET_MESH_SHAPE": "1,2"})
    solo = ReplicaSupervisor({"models": []}, replicas=1, ports=[3])
    assert solo._chip_env(solo.replicas[0],
                          {"MXNET_MESH_SHAPE": "1,2"}) == {}


def test_supervisor_start_refuses_more_replicas_than_chips(monkeypatch):
    from mxnet_tpu import context
    from mxnet_tpu.serving.supervisor import ReplicaSupervisor
    monkeypatch.setattr(context, "host_chip_count", lambda: 1)
    sup = ReplicaSupervisor({"models": []}, replicas=2, ports=[1, 2],
                            env={"JAX_PLATFORMS": ""})
    with pytest.raises(RuntimeError, match="no chip 1: this host has 1"):
        sup.start()
    assert all(r.proc is None for r in sup.replicas)    # nothing started
    assert sup._spec_path is None


# ---------------------------------------------------------------------------
# native runtime: built from what git tracks
# ---------------------------------------------------------------------------
def test_native_staleness_is_a_source_digest(tmp_path, monkeypatch):
    if _native.lib() is None:
        pytest.skip("no native runtime here: %s" % _native.build_error)
    assert not _native._stale()
    os.utime(os.path.join(_native._SRC_DIR, "mxtpu", "engine.cc"))
    assert not _native._stale()                 # mtimes say nothing
    lib = tmp_path / "libmxtpu_core.so"
    lib.write_bytes(b"")
    monkeypatch.setattr(_native, "_LIB_PATH", str(lib))
    assert _native._stale()                     # no digest beside it
    (tmp_path / "libmxtpu_core.so.src").write_text(_native._src_digest())
    assert not _native._stale()
    (tmp_path / "libmxtpu_core.so.src").write_text("other sources")
    assert _native._stale()
