"""setup_compile_s: host seconds of set-up spent compiling.

The length of the union of JAX's compile spans (tracing to a jaxpr,
lowering to MLIR, the backend compile with its persistent-cache read;
`compile_time.CompileClock`) from process start to the window's start,
handed in as `ctx["compile_s"]`. None where the run did not record them."""


def read(ctx):
    return ctx.get("compile_s")
