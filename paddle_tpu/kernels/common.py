"""What every Pallas kernel file here shares: whether the kernels can run
(a TPU, or the interpreter asked for), and the one ``pl.pallas_call`` of
the package, which names the kernel for the trace."""

import os

import jax
from jax.experimental import pallas as pl


def interpret():
    """PADDLE_TPU_PALLAS_INTERPRET=1 runs the kernels through the pallas
    interpreter (CPU CI exercises the real kernel bodies). On a TPU it
    is an error, not a mode: a stray setting would leave the chip idle
    behind the interpreter and say nothing."""
    on = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "") == "1"
    if on and jax.devices()[0].platform == "tpu":
        raise RuntimeError(
            "PADDLE_TPU_PALLAS_INTERPRET=1 on a tpu platform: the Pallas "
            "kernels would run interpreted instead of compiled; unset it")
    return on


def supports_pallas():
    return interpret() or jax.devices()[0].platform == "tpu"


def named_pallas_call(name, kernel, **kw):
    """``pl.pallas_call`` under ``name``, given twice: as ``name=`` and as
    a ``named_scope`` round the call. XLA names the custom call after the
    innermost scope it sits in, and under a transform the outermost scope
    reads ``jvp(<name>)``; with two, the inner one stays plain whatever
    the call was traced under. ``name=`` also goes into the Mosaic module,
    so a compilation cache keyed on the program without its debug info
    cannot serve the unnamed kernel in place of this one."""
    call = pl.pallas_call(kernel, name=name, interpret=interpret(), **kw)

    def named(*args):
        with jax.named_scope(name):
            return call(*args)

    return named
