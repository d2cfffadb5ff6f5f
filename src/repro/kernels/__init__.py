"""Pallas TPU kernels for the framework's compute hot spots.

- triangle_count: blocked sum((A@B) * M) -- the paper's counting phase on the
  MXU (DESIGN.md §2). This is the kernel the dense dynamic-pipeline ring calls
  per streamed block.
- flash_attention: causal fused attention for the LM architectures.
- embedding_bag: gather + segment-reduce for the recsys embedding hot path.

Each kernel ships ops.py (jit'd wrapper; whether it runs compiled or
interpreted is ``repro.utils.interpret_mode``'s call — compiled on a TPU
backend, interpreted elsewhere) and ref.py (pure-jnp oracle used by the allclose
sweeps in tests/).
"""

_TRACED: list[tuple[str, str]] = []


def note_kernel_trace(name: str, interpret: bool) -> None:
    """Trace-time telemetry: one record per trace of a kernel wrapper,
    ``(name, "compiled" | "interpret")``. A jit-cache hit traces nothing,
    so records mark first uses of a shape, like
    ``core.streaming.ingest_trace_count``."""
    _TRACED.append((name, "interpret" if interpret else "compiled"))


def kernel_traces() -> list[tuple[str, str]]:
    """Every kernel trace recorded so far in this process, oldest first."""
    return list(_TRACED)
