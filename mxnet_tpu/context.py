"""Device contexts for the TPU-native framework.

Parity: reference `python/mxnet/context.py` and `include/mxnet/base.h:92`
(``Context{dev_type, dev_id}``).  The reference enumerates kCPU/kGPU/
kCPUPinned/kCPUShared; here the accelerator type is ``tpu`` and devices
resolve to JAX/PJRT devices.  ``mx.gpu(i)`` is kept as a compatibility alias
for ``mx.tpu(i)`` so reference scripts run unmodified.
"""
from __future__ import annotations

import glob
import threading

import jax

_DEV_TYPES = ("cpu", "tpu", "cpu_pinned", "cpu_shared")


class Context:
    """A device context (device_type, device_id).

    Supports use as a ``with`` block to set the default context, matching
    reference ``python/mxnet/context.py`` semantics.
    """

    _default = threading.local()

    def __init__(self, device_type, device_id=0):
        if device_type == "gpu":  # compat alias: reference scripts say mx.gpu(i)
            device_type = "tpu"
        if device_type not in _DEV_TYPES:
            raise ValueError("unknown device_type %r" % (device_type,))
        self.device_type = device_type
        self.device_id = device_id
        self._old = []

    # -- resolution to a PJRT device -------------------------------------
    @property
    def jax_device(self):
        """Resolve to a jax.Device.

        ``tpu(i)`` is device ``i`` of JAX's default backend, and an ``i``
        the backend does not have is an error.  On a host with no
        accelerator the default backend is the CPU, so ``tpu(i)`` names
        CPU device ``i`` there: that much CPU resolution stays because
        tier-1 runs the whole suite, ``mx.tpu()``/``mx.gpu()`` contexts
        included, on the forced-CPU mesh of ``tests/conftest.py``.  A
        backend that fails to initialise raises; nothing falls to CPU."""
        if self.device_type == "tpu":
            devs = jax.devices()
            if not 0 <= self.device_id < len(devs):
                raise ValueError(
                    "%r: the %s backend has %d device(s)"
                    % (self, devs[0].platform, len(devs)))
            return devs[self.device_id]
        devs = jax.devices("cpu")
        return devs[self.device_id % len(devs)]

    # -- comparison / hashing --------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    # -- scoping ----------------------------------------------------------
    def __enter__(self):
        self._old.append(getattr(Context._default, "ctx", None))
        Context._default.ctx = self
        return self

    def __exit__(self, *exc):
        Context._default.ctx = self._old.pop()
        return False

    def empty_cache(self):
        """Best effort HBM cache release (reference: Context.empty_cache)."""
        for d in jax.live_arrays():
            pass  # PJRT owns pooling; nothing to free eagerly.


# Device is the mxnet-2.0 name for Context (python/mxnet/device.py)
Device = Context


def cpu(device_id=0):
    return Context("cpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Compatibility alias — maps to the TPU context."""
    return Context("tpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def current_context():
    ctx = getattr(Context._default, "ctx", None)
    if ctx is None:
        ctx = Context("tpu", 0) if num_tpus() else Context("cpu", 0)
        Context._default.ctx = ctx
    return ctx


current_device = current_context


def num_tpus():
    """Number of accelerator devices visible (reference: mx.context.num_gpus)."""
    return sum(1 for d in jax.devices() if d.platform != "cpu")


num_gpus = num_tpus


def device_count():
    return len(jax.devices())


def host_chip_count():
    """TPU chips this host hands to a process, counted without
    initialising a JAX backend so a launcher that must stay off the
    device can call it: the device nodes libtpu opens, ``/dev/accel*``
    or one ``/dev/vfio/<group>`` per chip.  0 on a host with no TPU.
    (The PCI bus is not the count: the one-chip v5e machine is a slice
    of a four-chip host, shows four Google devices there and one
    ``/dev/vfio/3``; PR 21.)"""
    return len(glob.glob("/dev/accel*")
               + glob.glob("/dev/vfio/[0-9]*"))


def must_place_children(env):
    """Whether a launcher whose children inherit ``env`` has to show
    each of them its own chip: not where they are held to the CPU
    (``JAX_PLATFORMS=cpu``, the tier-1 lane), not where ``env`` already
    says ``TPU_VISIBLE_CHIPS`` (the operator placed them: theirs wins),
    and not on a host with no TPU."""
    return not (env.get("JAX_PLATFORMS", "").startswith("cpu")
                or "TPU_VISIBLE_CHIPS" in env or not host_chip_count())


def chip_visibility_env(chip, port):
    """Environment that shows a child process ONE chip of this host.

    A TPU belongs to one process at a time, and a process takes every
    chip it can see: the first child of a multi-process launch (fleet
    replicas, kvstore workers) would hold them all.  These are libtpu's
    own variables (the set jax's multi-process tests stamp); the parent
    that builds them must itself stay off JAX.  ``port`` is a free port
    the caller reserved for the child's TPU runtime, so two launchers on
    one host do not meet.  A ``chip`` the host does not have is an
    error here, in the launcher, not a crash loop in the child."""
    chip, n = int(chip), host_chip_count()
    if not 0 <= chip < n:
        raise RuntimeError(
            "no chip %d: this host has %d TPU chip(s), and a chip "
            "belongs to one process at a time" % (chip, n))
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": "localhost:%d" % port,
            "TPU_PROCESS_PORT": str(port),
            "CLOUD_TPU_TASK_ID": "0",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


def tpu_memory_info(device_id=0):
    """(free_bytes, total_bytes) for one accelerator device.

    Parity: mx.context.gpu_memory_info (python/mxnet/context.py →
    MXGetGPUMemoryInformation64).  Backed by the PJRT allocator stats when
    available, else the live-buffer census (profiler.device_memory_stats);
    total comes from the chip-spec table / MXNET_TPU_HBM_BYTES."""
    from . import profiler
    devs = [d for d in jax.devices() if d.platform != "cpu"] or jax.devices()
    d = devs[device_id]
    st = profiler.device_memory_stats(d)
    total = st.get("bytes_limit") or 0
    return max(total - st["bytes_in_use"], 0), total


gpu_memory_info = tpu_memory_info
