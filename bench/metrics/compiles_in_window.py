"""Compiles that started in the window: the ``compile`` spans the tracer
records from JAX's jaxpr-lowering event (``repro.obs.trace.COMPILE_EVENT``),
the event the harness counts for its own diagnostic."""

from rmqbench.spans import named, records_hook


def read(ctx):
    return len(named(ctx, "compile")) if records_hook("compile") else None
