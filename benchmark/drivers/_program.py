"""What both drivers ask of a compiled step program (after chip_smoke.py's
own checks): its abstract signature, whether a kernel is a Mosaic call in it,
and what it holds on the device."""
from __future__ import annotations


def abstract(args):
    """Shape, dtype and (for committed arrays) sharding of a call's
    arguments, to lower the same program again."""
    import jax

    def one(a):
        placed = isinstance(a, jax.Array) and a.committed
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=a.sharding if placed else None)

    return jax.tree.map(one, args)


def mosaic_calls(compiled, kernels):
    """``{kernel: Mosaic custom calls of it in the compiled program}``."""
    hlo = compiled.as_text().splitlines()
    return {k: sum('custom_call_target="tpu_custom_call"' in line
                   and k in line for line in hlo) for k in kernels}


def program_bytes(compiled):
    """Device bytes the compiled program holds while it runs: arguments,
    outputs that are not donated arguments, and temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
