"""Public entry points for the Pallas kernels.

The one place that chooses how a kernel runs: compiled on a TPU backend,
through the Pallas interpreter everywhere else (the CPU test hosts).
The kernel modules take ``interpret`` as a required keyword, so no call
path can fall back to the interpreter by default. All wrappers share
signatures with the pure-jnp oracles in ref.py.
"""
from __future__ import annotations

import jax

from repro.kernels.cong_update import cong_update as _cong_update
from repro.kernels.lcmp_decide import P_PAD
from repro.kernels.lcmp_decide import lcmp_decide as _lcmp_decide
from repro.kernels.qsr_int8 import qsr_dequant as _qsr_dequant
from repro.kernels.qsr_int8 import qsr_int8 as _qsr_int8


def interpret() -> bool:
    return jax.default_backend() != "tpu"


def lcmp_decide(flow_ids, c_path, c_cong, valid, params=None):
    from repro.core.select import SelectParams
    if c_path.shape[-1] > P_PAD:
        raise ValueError(f"lcmp_decide takes at most {P_PAD} candidates "
                         f"per flow (paper §4: m <= 8), got {c_path.shape[-1]}")
    return _lcmp_decide(flow_ids, c_path, c_cong, valid,
                        params or SelectParams(), interpret=interpret())


def cong_update(state, queue_cells, now_us, tables, params=None):
    from repro.core.cong import CongParams
    return _cong_update(state, queue_cells, now_us, tables,
                        params or CongParams(), interpret=interpret())


def qsr_int8(x, rand_bits):
    return _qsr_int8(x, rand_bits, interpret=interpret())


def qsr_dequant(q, scales):
    return _qsr_dequant(q, scales, interpret=interpret())
