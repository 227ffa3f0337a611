"""JAX's spans of building a program (a jaxpr trace, an MLIR lowering, a
backend compile or a load from the persistent compilation cache) that fall
inside the measured window: the compiled step, a Tier-1/1.5 re-jit of
``train/loop.py::compile_step``, or any other program.  0 on a sound run."""


def read(ctx):
    return ctx["compiles"]
