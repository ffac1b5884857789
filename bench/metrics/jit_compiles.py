"""JAX compiles in the window, of every program: the count of the store's
``jit.compile`` spans (a backend compile or a persistent-cache load).
None where the store records no JAX compile steps at all (no ``jit.*``
span anywhere in the run, and no ``compact.dispatch`` span either: a
build without the listener), so an absent listener does not read as 0."""


def read(run):
    if not any(n.startswith("jit.") or n == "compact.dispatch"
               for n, _, _ in run.spans):
        return None
    return len(run.span_seconds("jit.compile"))
