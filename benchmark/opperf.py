#!/usr/bin/env python
"""Operator micro-benchmark harness — FULL registered-op surface.

Parity: reference `benchmark/opperf/opperf.py`, which enumerates every
registered operator, auto-generates inputs, and records fwd / fwd+bwd
latencies as the perf-regression surface (SURVEY.md §4/§6).

This harness walks the live op namespaces (mx.np, mx.npx, np.linalg,
np.random, contrib.ops), synthesizes arguments per op (generic probing +
an override table for shape/axis/index-taking ops), and times each op's
eager dispatch:

  fwd:      async dispatches, one sync per window (steady-state eager
            cost; a sync per op would measure the host-fetch RTT)
  fwd+bwd:  autograd.record + backward per iteration, same discipline

Medians are taken across windows (robust against interference from
whatever else shares the host).

Usage:
  python benchmark/opperf.py                    # full surface
  python benchmark/opperf.py --ops np:add,npx:softmax
  python benchmark/opperf.py --json OPPERF.json
  python benchmark/opperf.py --probe-only       # coverage report only
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu import np as mxnp
from mxnet_tpu import npx
from mxnet_tpu.ndarray import ndarray


# ---------------------------------------------------------------------------
# argument synthesis
# ---------------------------------------------------------------------------
N = 256          # square matrix edge
V = 4096         # vector length
IMG = (8, 16, 28, 28)


def _u(shape, dtype="float32"):
    a = mxnp.random.uniform(low=0.1, high=1.0, size=shape)
    return a.astype(dtype) if dtype != "float32" else a


def _idx(n, hi):
    return mxnp.array(onp.random.RandomState(0).randint(0, hi, size=n))


def _spd():
    m = onp.random.RandomState(0).randn(32, 32).astype("float32")
    return mxnp.array(m @ m.T + 32 * onp.eye(32, dtype="float32"))


# ops that are not benchable ops (array constructors from python data,
# introspection, host-sync utilities, aliases of the ndarray class, ...)
EXCLUDE = {
    "np": {"array", "asarray", "ascontiguousarray", "asnumpy", "apply_op",
           "astype", "copyto", "dtype", "empty", "empty_like", "finfo",
           "iinfo", "from_numpy", "frombuffer", "fromfunction", "get_include",
           "issubdtype", "may_share_memory", "shares_memory", "ndarray",
           "newaxis", "result_type", "promote_types", "save", "savez",
           "load", "seterr", "set_printoptions", "shape", "size", "ndim",
           "broadcast_shapes", "can_cast", "min_scalar_type", "isscalar",
           "iterable", "printoptions", "typename", "waitall", "abs_",
           "bool", "bool_", "set_module"},
    "npx": {"set_np", "reset_np", "use_np", "use_np_shape", "use_np_array",
            "is_np_array", "is_np_shape", "np_shape", "np_array", "npx",
            "waitall", "load", "save", "savez", "seed", "current_device",
            "num_gpus", "gpu", "gpu_memory_info", "cpu", "cpu_pinned"},
    "linalg": set(),
    "random": {"seed", "default_rng", "get_state", "set_state"},
    "contrib": set(),
}

# per-op argument overrides: name -> (args_thunk, needs_grad) | None to
# skip with a documented reason (thunks make fresh buffers per run)
OVERRIDES = {
    # creation / shape-taking
    "np:zeros": (lambda: (((N, N),), {}), False),
    "np:ones": (lambda: (((N, N),), {}), False),
    "np:full": (lambda: (((N, N), 3.14), {}), False),
    "np:eye": (lambda: ((N,), {}), False),
    "np:identity": (lambda: ((N,), {}), False),
    "np:arange": (lambda: ((V,), {}), False),
    "np:linspace": (lambda: ((0.0, 1.0, V), {}), False),
    "np:logspace": (lambda: ((0.0, 3.0, V), {}), False),
    "np:tri": (lambda: ((N,), {}), False),
    "np:indices": (lambda: (((32, 32),), {}), False),
    "np:bartlett": (lambda: ((V,), {}), False),
    "np:blackman": (lambda: ((V,), {}), False),
    "np:hamming": (lambda: ((V,), {}), False),
    "np:hanning": (lambda: ((V,), {}), False),
    "np:kaiser": (lambda: ((V, 14.0), {}), False),
    # reshape / movement
    "np:reshape": (lambda: ((_u((N, N)), (N * N,)), {}), True),
    "np:swapaxes": (lambda: ((_u((8, 16, 32)), 0, 2), {}), True),
    "np:moveaxis": (lambda: ((_u((8, 16, 32)), 0, 2), {}), True),
    "np:rollaxis": (lambda: ((_u((8, 16, 32)), 2), {}), True),
    "np:expand_dims": (lambda: ((_u((N, N)), 0), {}), True),
    "np:squeeze": (lambda: ((_u((1, N, N)),), {}), True),
    "np:rot90": (lambda: ((_u((N, N)),), {}), True),
    "np:roll": (lambda: ((_u((N, N)), 3), {}), True),
    "np:tile": (lambda: ((_u((64, 64)), (2, 2)), {}), True),
    "np:repeat": (lambda: ((_u((N, N)), 2), {}), True),
    "np:broadcast_to": (lambda: ((_u((1, N)), (N, N)), {}), True),
    "np:broadcast_arrays": (lambda: (([_u((1, N)), _u((N, 1))],), {}),
                            False),
    # joining / splitting
    "np:concatenate": (lambda: (([_u((N, N)), _u((N, N))],), {}), True),
    "np:stack": (lambda: (([_u((N, N)), _u((N, N))],), {}), True),
    "np:vstack": (lambda: (([_u((N, N)), _u((N, N))],), {}), True),
    "np:hstack": (lambda: (([_u((N, N)), _u((N, N))],), {}), True),
    "np:dstack": (lambda: (([_u((N, N)), _u((N, N))],), {}), True),
    "np:column_stack": (lambda: (([_u((N,)), _u((N,))],), {}), True),
    "np:row_stack": (lambda: (([_u((N, N)), _u((N, N))],), {}), True),
    "np:split": (lambda: ((_u((N, N)), 4), {}), False),
    "np:array_split": (lambda: ((_u((N, N)), 4), {}), False),
    "np:hsplit": (lambda: ((_u((N, N)), 4), {}), False),
    "np:vsplit": (lambda: ((_u((N, N)), 4), {}), False),
    "np:dsplit": (lambda: ((_u((8, 8, 8)), 4), {}), False),
    "np:append": (lambda: ((_u((N, N)), _u((N, N))), {}), True),
    "np:insert": (lambda: ((_u((V,)), 5, 1.0), {}), False),
    "np:delete": (lambda: ((_u((V,)), 5), {}), False),
    "np:pad": (lambda: ((_u((N, N)), 2), {}), True),
    # indexing
    "np:take": (lambda: ((_u((N, N)), _idx(64, N)), {"axis": 0}), True),
    "np:take_along_axis": (
        lambda: ((_u((N, N)), _idx(N, N).reshape(1, N).astype("int64")),
                 {}), False),
    "np:put_along_axis": None,  # in-place host semantics
    "np:choose": None,
    "np:compress": (lambda: ((mxnp.array([True] * 32), _u((N, N))),
                             {"axis": 0}), False),
    "np:extract": (lambda: ((_u((N, N)) > 0.5, _u((N, N))), {}), False),
    "np:where": (lambda: ((_u((N, N)) > 0.5, _u((N, N)), _u((N, N))),
                          {}), True),
    "np:select": (lambda: (([_u((V,)) > 0.5], [_u((V,))]), {}), False),
    "np:searchsorted": (lambda: ((mxnp.sort(_u((V,))), _u((64,))), {}),
                        False),
    "np:bincount": (lambda: ((_idx(V, 64).astype("int32"),), {}), False),
    "np:digitize": (lambda: ((_u((V,)), mxnp.sort(_u((16,)))), {}), False),
    "np:unravel_index": (lambda: ((_idx(64, N * N), (N, N)), {}), False),
    "np:ravel_multi_index": (
        lambda: (((_idx(64, N), _idx(64, N)), (N, N)), {}), False),
    "np:diag": (lambda: ((_u((N, N)),), {}), True),
    "np:diagonal": (lambda: ((_u((N, N)),), {}), True),
    "np:diagflat": (lambda: ((_u((64,)),), {}), True),
    "np:diag_indices_from": (lambda: ((_u((N, N)),), {}), False),
    "np:tril": (lambda: ((_u((N, N)),), {}), True),
    "np:triu": (lambda: ((_u((N, N)),), {}), True),
    "np:tril_indices": (lambda: ((64,), {}), False),
    "np:trace": (lambda: ((_u((N, N)),), {}), True),
    "np:nonzero": (lambda: ((_u((N, N)) > 0.5,), {}), False),
    "np:flatnonzero": (lambda: ((_u((V,)) > 0.5,), {}), False),
    "np:argwhere": (lambda: ((_u((N, N)) > 0.5,), {}), False),
    "np:count_nonzero": (lambda: ((_u((N, N)) > 0.5,), {}), False),
    "np:unique": (lambda: ((_idx(V, 64),), {}), False),
    "np:isin": (lambda: ((_idx(V, 64), _idx(16, 64)), {}), False),
    "np:in1d": (lambda: ((_idx(V, 64), _idx(16, 64)), {}), False),
    "np:intersect1d": (lambda: ((_idx(V, 64), _idx(V, 64)), {}), False),
    "np:union1d": (lambda: ((_idx(V, 64), _idx(V, 64)), {}), False),
    "np:setdiff1d": (lambda: ((_idx(V, 64), _idx(16, 64)), {}), False),
    "np:setxor1d": (lambda: ((_idx(V, 64), _idx(V, 64)), {}), False),
    "np:trim_zeros": (lambda: ((mxnp.array([0.0, 1, 2, 0]),), {}), False),
    # matmul family
    "np:dot": (lambda: ((_u((N, N)), _u((N, N))), {}), True),
    "np:matmul": (lambda: ((_u((N, N)), _u((N, N))), {}), True),
    "np:inner": (lambda: ((_u((N, N)), _u((N, N))), {}), True),
    "np:outer": (lambda: ((_u((V,)), _u((V,))), {}), True),
    "np:vdot": (lambda: ((_u((V,)), _u((V,))), {}), True),
    "np:cross": (lambda: ((_u((V, 3)), _u((V, 3))), {}), True),
    "np:kron": (lambda: ((_u((16, 16)), _u((16, 16))), {}), True),
    "np:tensordot": (lambda: ((_u((N, N)), _u((N, N))), {}), True),
    "np:einsum": (lambda: (("ij,jk->ik", _u((N, N)), _u((N, N))), {}),
                  False),
    # reductions / stats needing special args
    "np:percentile": (lambda: ((_u((N, N)), 50.0), {}), False),
    "np:quantile": (lambda: ((_u((N, N)), 0.5), {}), False),
    "np:nanpercentile": (lambda: ((_u((N, N)), 50.0), {}), False),
    "np:nanquantile": (lambda: ((_u((N, N)), 0.5), {}), False),
    "np:histogram": (lambda: ((_u((V,)),), {}), False),
    "np:correlate": (lambda: ((_u((V,)), _u((64,))), {}), False),
    "np:convolve": (lambda: ((_u((V,)), _u((64,))), {}), False),
    "np:cov": (lambda: ((_u((16, V)),), {}), False),
    "np:corrcoef": (lambda: ((_u((16, V)),), {}), False),
    "np:gradient": (lambda: ((_u((V,)),), {}), False),
    "np:diff": (lambda: ((_u((N, N)),), {}), True),
    "np:ediff1d": (lambda: ((_u((V,)),), {}), True),
    "np:trapz": (lambda: ((_u((V,)),), {}), False),
    "np:interp": (lambda: ((_u((V,)), mxnp.sort(_u((64,))), _u((64,))),
                           {}), False),
    "np:meshgrid": (lambda: ((_u((64,)), _u((64,))), {}), False),
    # int / bool semantics
    "np:left_shift": (lambda: ((_idx(V, 8).astype("int32"), 2), {}), False),
    "np:right_shift": (lambda: ((_idx(V, 8).astype("int32"), 2), {}),
                       False),
    "np:bitwise_and": (lambda: ((_idx(V, 64).astype("int32"),
                                 _idx(V, 64).astype("int32")), {}), False),
    "np:bitwise_or": (lambda: ((_idx(V, 64).astype("int32"),
                                _idx(V, 64).astype("int32")), {}), False),
    "np:bitwise_xor": (lambda: ((_idx(V, 64).astype("int32"),
                                 _idx(V, 64).astype("int32")), {}), False),
    "np:bitwise_not": (lambda: ((_idx(V, 64).astype("int32"),), {}), False),
    "np:invert": (lambda: ((_idx(V, 64).astype("int32"),), {}), False),
    "np:logical_and": (lambda: ((_u((N, N)) > 0.5, _u((N, N)) > 0.5), {}),
                       False),
    "np:logical_or": (lambda: ((_u((N, N)) > 0.5, _u((N, N)) > 0.5), {}),
                      False),
    "np:logical_xor": (lambda: ((_u((N, N)) > 0.5, _u((N, N)) > 0.5), {}),
                       False),
    "np:logical_not": (lambda: ((_u((N, N)) > 0.5,), {}), False),
    "np:gcd": (lambda: ((_idx(V, 100).astype("int32"),
                         _idx(V, 100).astype("int32")), {}), False),
    "np:lcm": (lambda: ((_idx(V, 100).astype("int32"),
                         _idx(V, 100).astype("int32")), {}), False),
    "np:ldexp": (lambda: ((_u((V,)), _idx(V, 8).astype("int32")), {}),
                 False),
    "np:divmod": (lambda: ((_u((V,)), 0.3), {}), False),
    "np:modf": (lambda: ((_u((V,)),), {}), False),
    "np:isclose": (lambda: ((_u((N, N)), _u((N, N))), {}), False),
    "np:allclose": (lambda: ((_u((N, N)), _u((N, N))), {}), False),
    "np:array_equal": (lambda: ((_u((N, N)), _u((N, N))), {}), False),
    "np:array_equiv": (lambda: ((_u((N, N)), _u((N, N))), {}), False),
    "np:clip": (lambda: ((_u((N, N)), 0.2, 0.8), {}), True),
    "np:heaviside": (lambda: ((_u((V,)), 0.5), {}), False),
    "np:copysign": (lambda: ((_u((V,)), _u((V,))), {}), False),
    "np:nextafter": (lambda: ((_u((V,)), _u((V,))), {}), False),
    "np:partition": (lambda: ((_u((V,)), 64), {}), False),
    "np:argpartition": (lambda: ((_u((V,)), 64), {}), False),
    "np:lexsort": (lambda: (((_u((V,)), _u((V,))),), {}), False),
    "np:vander": (lambda: ((_u((64,)),), {}), False),
    "np:polyval": (lambda: ((_u((8,)), _u((V,))), {}), False),
    "np:cumprod": (lambda: ((_u((N, N)),), {}), True),
    "np:nancumprod": (lambda: ((_u((N, N)),), {}), False),
    "np:nancumsum": (lambda: ((_u((N, N)),), {}), False),
    "np:resize": (lambda: ((_u((N, N)), (64, 64)), {}), False),
    "np:rot90": (lambda: ((_u((N, N)),), {}), True),
    "np:triu_indices": (lambda: ((64,), {}), False),
    "np:triu_indices_from": (lambda: ((_u((64, 64)),), {}), False),
    "np:tril_indices_from": (lambda: ((_u((64, 64)),), {}), False),
    # linalg
    "linalg:cholesky": (lambda: ((_spd(),), {}), False),
    "linalg:inv": (lambda: ((_spd(),), {}), False),
    "linalg:pinv": (lambda: ((_u((64, 32)),), {}), False),
    "linalg:det": (lambda: ((_spd(),), {}), False),
    "linalg:slogdet": (lambda: ((_spd(),), {}), False),
    "linalg:eig": (lambda: ((_spd(),), {}), False),
    "linalg:eigh": (lambda: ((_spd(),), {}), False),
    "linalg:eigvals": (lambda: ((_spd(),), {}), False),
    "linalg:eigvalsh": (lambda: ((_spd(),), {}), False),
    "linalg:qr": (lambda: ((_u((64, 64)),), {}), False),
    "linalg:svd": (lambda: ((_u((64, 64)),), {}), False),
    "linalg:solve": (lambda: ((_spd(), _u((32, 4))), {}), False),
    "linalg:lstsq": (lambda: ((_u((64, 32)), _u((64,))), {"rcond": None}),
                     False),
    "linalg:norm": (lambda: ((_u((N, N)),), {}), True),
    "linalg:cond": (lambda: ((_spd(),), {}), False),
    "linalg:matrix_rank": (lambda: ((_u((64, 64)),), {}), False),
    "linalg:matrix_power": (lambda: ((_u((64, 64)), 3), {}), False),
    "linalg:multi_dot": (lambda: (([_u((N, N)), _u((N, N)), _u((N, N))],),
                                  {}), False),
    "linalg:tensorinv": (lambda: ((_u((8, 8, 8, 8)),), {}), False),
    "linalg:tensorsolve": (lambda: ((_u((8, 8, 8, 8)), _u((8, 8))), {}),
                           False),
    "linalg:matmul": (lambda: ((_u((N, N)), _u((N, N))), {}), True),
    "linalg:potrf": (lambda: ((_spd(),), {}), False),
    # random (sampling: fwd-only)
    "random:uniform": (lambda: ((0.0, 1.0, (N, N)), {}), False),
    "random:normal": (lambda: ((0.0, 1.0, (N, N)), {}), False),
    "random:randn": (lambda: ((N, N), {}), False),
    "random:rand": (lambda: ((N, N), {}), False),
    "random:randint": (lambda: ((0, 100, (N, N)), {}), False),
    "random:random": (lambda: (((N, N),), {}), False),
    "random:random_sample": (lambda: (((N, N),), {}), False),
    "random:ranf": (lambda: (((N, N),), {}), False),
    "random:sample": (lambda: (((N, N),), {}), False),
    "random:exponential": (lambda: ((1.0, (N, N)), {}), False),
    "random:gamma": (lambda: ((2.0, 1.0, (N, N)), {}), False),
    "random:beta": (lambda: ((2.0, 3.0, (N, N)), {}), False),
    "random:chisquare": (lambda: ((2.0, (N, N)), {}), False),
    "random:poisson": (lambda: ((2.0, (N, N)), {}), False),
    "random:laplace": (lambda: ((0.0, 1.0, (N, N)), {}), False),
    "random:gumbel": (lambda: ((0.0, 1.0, (N, N)), {}), False),
    "random:logistic": (lambda: ((0.0, 1.0, (N, N)), {}), False),
    "random:lognormal": (lambda: ((0.0, 1.0, (N, N)), {}), False),
    "random:pareto": (lambda: ((2.0, (N, N)), {}), False),
    "random:power": (lambda: ((2.0, (N, N)), {}), False),
    "random:rayleigh": (lambda: ((1.0, (N, N)), {}), False),
    "random:weibull": (lambda: ((2.0, (N, N)), {}), False),
    "random:binomial": (lambda: ((10, 0.5, (N, N)), {}), False),
    "random:negative_binomial": (lambda: ((10, 0.5, (N, N)), {}), False),
    "random:geometric": (lambda: ((0.5, (N, N)), {}), False),
    "random:multinomial": (lambda: ((10, [0.25] * 4, (V,)), {}), False),
    "random:dirichlet": (lambda: (([1.0, 2.0, 3.0], (V,)), {}), False),
    "random:multivariate_normal": (
        lambda: ((mxnp.zeros(4), mxnp.array(onp.eye(4, dtype="float32")),
                  (V,)), {}), False),
    "random:choice": (lambda: ((V, (64,)), {}), False),
    "random:permutation": (lambda: ((V,), {}), False),
    "random:shuffle": (lambda: ((_u((V,)),), {}), False),
    "random:bernoulli": (lambda: ((0.5,), {"size": (N, N)}), False),
    "random:triangular": (lambda: ((0.0, 0.5, 1.0, (N, N)), {}), False),
    "random:f": (lambda: ((2.0, 3.0, (N, N)), {}), False),
    "random:standard_t": (lambda: ((3.0, (N, N)), {}), False),
    "random:standard_cauchy": (lambda: (((N, N),), {}), False),
    "random:standard_exponential": (lambda: (((N, N),), {}), False),
    "random:standard_gamma": (lambda: ((2.0, (N, N)), {}), False),
    "random:standard_normal": (lambda: (((N, N),), {}), False),
    "random:vonmises": (lambda: ((0.0, 1.0, (N, N)), {}), False),
    "random:wald": (lambda: ((1.0, 1.0, (N, N)), {}), False),
    "random:zipf": (lambda: ((2.0, (N, N)), {}), False),
    "random:hypergeometric": (lambda: ((50, 50, 10, (N, N)), {}), False),
    "random:logseries": (lambda: ((0.5, (N, N)), {}), False),
    "random:noncentral_chisquare": (lambda: ((2.0, 1.0, (N, N)), {}),
                                    False),
    "random:noncentral_f": (lambda: ((2.0, 3.0, 1.0, (N, N)), {}), False),
    # npx
    "npx:fully_connected": (
        lambda: ((_u((128, N)), _u((N, N)), _u((N,))), {"num_hidden": N}),
        True),
    "npx:convolution": (
        lambda: ((_u(IMG), _u((16, 16, 3, 3))),
                 {"kernel": (3, 3), "pad": (1, 1), "num_filter": 16,
                  "no_bias": True}), True),
    "npx:deconvolution": (
        lambda: ((_u(IMG), _u((16, 16, 3, 3))),
                 {"kernel": (3, 3), "num_filter": 16, "no_bias": True}),
        False),
    "npx:pooling": (
        lambda: ((_u(IMG),), {"kernel": (2, 2), "stride": (2, 2)}), True),
    "npx:activation": (lambda: ((_u((N, N)),), {"act_type": "relu"}), True),
    "npx:batch_norm": (
        lambda: ((_u(IMG), _u((16,)), _u((16,)), _u((16,)), _u((16,))),
                 {"use_global_stats": True}), True),
    "npx:layer_norm": (
        lambda: ((_u((N, N)), _u((N,)), _u((N,))), {}), True),
    "npx:group_norm": (
        lambda: ((_u(IMG), _u((4,)), _u((4,))), {"num_groups": 4}), False),
    "npx:instance_norm": (
        lambda: ((_u(IMG), _u((16,)), _u((16,))), {}), False),
    "npx:l2_normalization": (lambda: ((_u((N, N)),), {}), False),
    "npx:lrn": (lambda: ((_u(IMG),), {"nsize": 5}), False),
    "npx:dropout": (lambda: ((_u((N, N)),), {"p": 0.5}), False),
    "npx:softmax": (lambda: ((_u((N, N)),), {}), True),
    "npx:log_softmax": (lambda: ((_u((N, N)),), {}), True),
    "npx:masked_softmax": (
        lambda: ((_u((N, N)), _u((N, N)) > 0.5), {}), False),
    "npx:softmin": (lambda: ((_u((N, N)),), {}), False),
    "npx:relu": (lambda: ((_u((N, N)),), {}), True),
    "npx:sigmoid": (lambda: ((_u((N, N)),), {}), True),
    "npx:smooth_l1": (lambda: ((_u((N, N)),), {}), False),
    "npx:embedding": (
        lambda: ((_idx(V, 1000), _u((1000, 64))),
                 {"input_dim": 1000, "output_dim": 64}), False),
    "npx:topk": (lambda: ((_u((N, N)),), {"k": 8}), False),
    "npx:pick": (lambda: ((_u((N, N)), _idx(N, N)), {}), False),
    "npx:one_hot": (lambda: ((_idx(V, 64),), {"depth": 64}), False),
    "npx:arange_like": (lambda: ((_u((N, N)),), {}), False),
    "npx:batch_dot": (lambda: ((_u((16, 64, 64)), _u((16, 64, 64))), {}),
                      True),
    "npx:erf": (lambda: ((_u((N, N)),), {}), True),
    "npx:erfinv": (lambda: ((_u((N, N)) * 0.9,), {}), False),
    "npx:reshape": (lambda: ((_u((N, N)), (-1,)), {}), False),
    "npx:reshape_like": (lambda: ((_u((N, N)), _u((N * N,))), {}), False),
    "npx:shape_array": (lambda: ((_u((N, N)),), {}), False),
    "npx:slice": (lambda: ((_u((N, N)),),
                           {"begin": (0, 0), "end": (64, 64)}), False),
    "npx:slice_axis": (lambda: ((_u((N, N)),),
                                {"axis": 0, "begin": 0, "end": 64}), False),
    "npx:slice_like": (lambda: ((_u((N, N)), _u((64, 64))), {}), False),
    "npx:gather_nd": (
        lambda: ((_u((N, N)), _idx(64, N).reshape(1, 64)), {}), False),
    "npx:sequence_mask": (
        lambda: ((_u((35, 32, 64)), mxnp.array([20.0] * 32)),
                 {"use_sequence_length": True}), False),
    "npx:sequence_last": (
        lambda: ((_u((35, 32, 64)), mxnp.array([20.0] * 32)),
                 {"use_sequence_length": True}), False),
    "npx:sequence_reverse": (
        lambda: ((_u((35, 32, 64)), mxnp.array([20.0] * 32)),
                 {"use_sequence_length": True}), False),
    "npx:rnn": None,         # exercised via the gluon.rnn bench row
    "npx:foreach": None,     # control flow: covered by bench_infer scan
    "npx:while_loop": None,
    "npx:cond": None,
    "npx:flash_attention": (
        lambda: ((_u((4, 8, 128, 64)), _u((4, 8, 128, 64)),
                  _u((4, 8, 128, 64))), {}), True),
    "npx:bias_gelu": (
        lambda: ((_u((128, N)), _u((N,))), {}), True),
    "npx:bias_dropout_residual": (
        lambda: ((_u((128, N)), _u((N,)), _u((128, N))), {"p": 0.1}), True),
    "npx:interleaved_matmul_selfatt_qk": (
        lambda: ((_u((128, 8, 3 * 64)),), {"heads": 8}), False),
    "npx:interleaved_matmul_selfatt_valatt": (
        lambda: ((_u((128, 8, 3 * 64)), _u((8 * 8, 128, 128))),
                 {"heads": 8}), False),
    "npx:cast": (lambda: ((_u((N, N)),), {"dtype": "float16"}), False),
    "npx:amp_cast": (lambda: ((_u((N, N)),), {"dtype": "float16"}), False),
    "npx:amp_multicast": None,
    "npx:all_finite": (lambda: ((_u((N, N)),), {}), False),
    "npx:norm": (lambda: ((_u((N, N)),), {}), False),
    "npx:ctc_loss": None,
}


def calibrate(repeats=5, inner=8):
    """Median ms of a fixed PURE-NUMPY workload (matmul + elementwise) —
    a machine/load probe, deliberately untouched by any framework code
    path.  The perf gate (tests/test_opperf_gate.py) divides its op
    ratios by (calibrate() now / the committed value in
    OPPERF_CALIB.json), so a loaded CI box — where every wall-clock
    measurement inflates together — no longer reads as a framework
    regression, while a real eager-path regression (framework-only, the
    5-20x class) still fails the normalized bars."""
    a = onp.random.RandomState(0).rand(256, 256).astype("float32")
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            b = a @ a
            b = onp.exp(b * 1e-3) + a
            b.sum()
        samples.append((time.perf_counter() - t0) / inner * 1e3)
    return statistics.median(samples)


def enumerate_ops():
    """(qualified_name, callable) across the live op namespaces."""
    from mxnet_tpu.contrib import ops as cops
    spaces = [("np", mxnp), ("npx", npx), ("linalg", mxnp.linalg),
              ("random", mxnp.random), ("contrib", cops)]
    out = []
    for prefix, mod in spaces:
        for name in sorted(dir(mod)):
            if name.startswith("_") or name in EXCLUDE.get(prefix, ()):
                continue
            fn = getattr(mod, name)
            if not callable(fn) or inspect.isclass(fn):
                continue
            out.append(("%s:%s" % (prefix, name), fn))
    return out


# generic probes tried in order when no override exists
GENERIC_PROBES = [
    (lambda: ((_u((N, N)),), {}), True),                 # unary float
    (lambda: ((_u((N, N)), _u((N, N))), {}), True),      # binary float
    (lambda: ((_u((N, N)), 2.0), {}), True),             # array + scalar
    (lambda: ((_u((V,)),), {}), True),                   # unary vector
]


def synthesize(qual, fn):
    """Return (args_thunk, needs_grad) or None if unsupported."""
    if qual in OVERRIDES:
        return OVERRIDES[qual]
    for thunk, grad in GENERIC_PROBES:
        try:
            args, kwargs = thunk()
            out = fn(*args, **kwargs)
            leaf = out[0] if isinstance(out, (tuple, list)) and out else out
            if isinstance(leaf, ndarray):
                leaf.wait_to_read()
            return (thunk, grad)
        except Exception:
            continue
    return None


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------
def _sync(out):
    if isinstance(out, (tuple, list)):
        for o in out:
            _sync(o)
    elif isinstance(out, ndarray):
        out.wait_to_read()


def bench_op(fn, args_thunk, needs_grad, warmup=3, iters=10, windows=3,
             agg="median"):
    """Median across windows of (window_time / iters); one sync per
    window (eager steady state is async dispatch, not host RTT).
    ``agg='min'`` takes the best window instead — interference (GC
    pauses, a competing lane's burst) only ever ADDS time, so min-of-N
    approaches the true dispatch cost; the perf gate's retry uses it."""
    from mxnet_tpu import engine
    pick = min if agg == "min" else statistics.median
    args, kwargs = args_thunk()
    nd_args = []
    for a in args:  # include arrays nested in list args (concat family)
        if isinstance(a, ndarray):
            nd_args.append(a)
        elif isinstance(a, (list, tuple)):
            nd_args.extend(x for x in a if isinstance(x, ndarray))

    fwd_samples = []
    with engine.bulk(1):
        for _ in range(warmup):
            out = fn(*args, **kwargs)
        _sync(out)
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args, **kwargs)
            _sync(out)
            fwd_samples.append((time.perf_counter() - t0) / iters * 1e3)
    fwd_ms = pick(fwd_samples)

    bwd_ms = None
    if needs_grad and nd_args:
        for a in nd_args:
            a.attach_grad()

        def run_bwd():
            with autograd.record():
                o = fn(*args, **kwargs)
                if isinstance(o, (tuple, list)):
                    o = o[0]
                loss = o.sum()
            loss.backward()
        try:
            bwd_samples = []
            for _ in range(warmup):
                run_bwd()
            nd_args[0].grad.wait_to_read()
            for _ in range(windows):
                t0 = time.perf_counter()
                for _ in range(iters):
                    run_bwd()
                nd_args[0].grad.wait_to_read()
                bwd_samples.append((time.perf_counter() - t0) / iters * 1e3)
            bwd_ms = pick(bwd_samples)
        except Exception:
            bwd_ms = None
    return fwd_ms, bwd_ms


def run(names=None, iters=10, probe_only=False, verbose=True,
        platform=None, windows=3, agg="median"):
    if platform:
        # must precede first backend use
        jax.config.update("jax_platforms", platform)
    mx.random.seed(0)
    ops = enumerate_ops()
    if names:
        sel = set(names)
        ops = [(q, f) for q, f in ops if q in sel or q.split(":")[1] in sel]
    rows, skipped = [], []
    for qual, fn in ops:
        spec = synthesize(qual, fn)
        if spec is None:
            skipped.append(qual)
            continue
        if probe_only:
            rows.append({"op": qual})
            continue
        try:
            fwd, bwd = bench_op(fn, spec[0], spec[1], iters=iters,
                                windows=windows, agg=agg)
        except Exception as e:
            skipped.append("%s (%s)" % (qual, type(e).__name__))
            continue
        rows.append({"op": qual, "fwd_ms": round(fwd, 4),
                     "fwd_bwd_ms": round(bwd, 4) if bwd else None})
        if verbose:
            print("%-40s %10.4f %10s" % (
                qual, fwd, "%.4f" % bwd if bwd else "n/a"), flush=True)
    return rows, skipped


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None, help="comma-separated subset")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--json", default=None)
    ap.add_argument("--probe-only", action="store_true",
                    help="report op coverage without timing")
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) before first use")
    args = ap.parse_args()

    names = args.ops.split(",") if args.ops else None
    rows, skipped = run(names, iters=args.iters,
                        probe_only=args.probe_only,
                        platform=args.platform)
    print("covered %d ops, skipped %d" % (len(rows), len(skipped)))
    if skipped:
        print("skipped:", ", ".join(sorted(skipped)))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print("wrote", args.json)


if __name__ == "__main__":
    main()
