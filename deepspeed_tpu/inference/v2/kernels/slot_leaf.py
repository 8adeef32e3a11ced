"""A cache leaf that a kernel updates in place by slot, kept in HBM.

The one-token kernels of the recurrent layers (``conv_update``,
``kda_state_update``, ``ssm_state_update``, ``retention_state_update``)
take a whole state leaf ``[layers, slots, ...]`` of which a launch moves
the rows' slots of one layer, and hand it back aliased. Where the leaf
LIES between launches is XLA's choice, and a blocked ``pallas_call``
says nothing about it: the compiler's memory-space assignment laid a
leaf that fits the chip's fast memory there (nemotron's convolution
leaf, 66.6 MB; granite's, 59.3; brumby's normaliser, 40.1) with a copy
of the WHOLE leaf in ahead of a launch and a copy back behind it, round
a kernel that moves a quarter of it. Neither ``memory_space=pl.ANY`` nor
``pltpu.HBM`` on the leaf's ``BlockSpec`` changes that (they say where
the KERNEL finds its operand, not where the program keeps it), and a
``cost_estimate`` only nudges the heuristic (PERF.md section 7, 2').

What does is the operand's COLOUR: ``pltpu.with_memory_space_constraint``
makes the value's type ``dtype<hbm>`` and pins the custom call's operand
to HBM, and the output aliased to it is declared ``pltpu.HBM`` in
``out_shape`` (XLA refuses a coloured operand whose aliased output is
not), which memory-space assignment obeys. The colour is part of a
value's TYPE, and nothing outside a kernel wants that type: a
``lax.scan`` or ``while_loop`` that carries the leaf needs one type in
and out, no result handler of a jitted program knows the coloured one,
and JAX's own operations refuse it. So a kernel colours its operand
with :func:`in_hbm` where it hands it to ``pallas_call`` and declares
its aliased output with :func:`hbm_out`; what ``pallas_call`` returns
is typed plain: the colour never leaves the kernel's function, and
every caller (the decode programs' walk of runs, a benchmark's loop, a
test) gets the placement without doing anything for it. (Do NOT colour
a cache where a program takes it, or again what a kernel returns: the
loop's carry is then ``dtype<hbm>`` on one side and ``dtype`` on the
other, and a program that returns the coloured value compiles and then
fails when it is called.)

The Pallas interpreter refuses the coloured type, and no backend but
the TPU's has the memory to speak of: :func:`in_hbm` and :func:`hbm_out`
leave the leaf plain unless the kernel is being compiled for a TPU.

One thing the colour asks of a caller, on a TPU: the leaf DONATED (or
carried by a loop), as every decode program of the engine takes its
cache. A jitted program that keeps its argument makes a copy of the leaf
for the kernel to update, the compiler wants that copy in fast memory
where it fits, and against the colour its memory-space assignment aborts
the process (``Check failed: ... Conflicting pending required
assignment``, libtpu of JAX 0.9.0) instead of giving way.
"""

import jax
from jax.experimental.pallas import tpu as pltpu


def _compiled_for_tpu(interpret) -> bool:
    return not interpret and jax.default_backend() == "tpu"


def in_hbm(leaf, interpret=False):
    """``leaf`` coloured HBM where the kernel that takes it is compiled
    for a TPU, else as it is."""
    if not _compiled_for_tpu(interpret):
        return leaf
    return pltpu.with_memory_space_constraint(leaf, pltpu.HBM)


def hbm_out(leaf, interpret=False):
    """The ``out_shape`` entry of the output aliased to ``leaf``: in HBM
    under the same condition as :func:`in_hbm`."""
    if not _compiled_for_tpu(interpret):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    return pltpu.HBM(leaf.shape, leaf.dtype)
