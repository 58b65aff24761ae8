"""Pallas TPU kernels — the hand-tuned hot-op tier.

Parity: this tier replaces the reference's cuDNN/fused-CUDA kernels
(`src/operator/contrib/transformer.cu`, `rnn-inl.h` cuDNN path, fusion RTC)
with TPU systolic-array kernels written in Pallas.

`fused_cell` is the persistent-kernel tier for latency-bound serial
loops: the LSTM time loop runs as one kernel launch a layer with
weights latched in VMEM.
"""
from __future__ import annotations

import os
import sys

import jax


def kernel_mode(var):
    """The one gate grammar of the ``MXNET_*`` kernel switches:
    ``0``/``off`` -> None (the XLA path), ``interpret`` -> "interpret"
    (the Pallas interpreter: the CPU test oracle), anything else ->
    "compiled" on a TPU backend and None on every other backend.

    There is no probe and no fallback behind this: a kernel selected
    here that Mosaic refuses fails the call with the compiler's message."""
    flag = os.environ.get(var, "").lower()
    if flag in ("0", "off", "false"):
        return None
    if flag == "interpret":
        return "interpret"
    return "compiled" if jax.default_backend() == "tpu" else None


def gspmd_config():
    """The ACTIVE ShardingConfig (more than one device) whose scope the op
    being traced is in, when GSPMD will partition that op: None without a
    config, and None inside a manual-collective region (a shard_map body,
    e.g. the ZeRO step), where operands are already per-shard local.

    Read through sys.modules so that a process which never built a config
    imports nothing for it."""
    mod = sys.modules.get("mxnet_tpu.parallel.shardcfg")
    if mod is None or mod.manual_mode():
        return None
    cfg = mod.current()
    return cfg if cfg is not None and cfg.active else None
