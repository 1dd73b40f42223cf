"""Reader kinds for per-layer metrics. A metric's file names one of
these modules under ``reader``; the module's ``read(spec, run)`` returns
the number, or ``None`` where it finds nothing to read (the metric is
then left out of the line: never 0 for a share of a roofline or a peak).

``run`` carries ``trace`` (``harness.trace.Trace``), ``counters`` (what
the loop and the harness counted), ``cfg``, ``workload``, ``model`` (the
family's glue), ``peaks``, ``chips`` and ``memory`` (the fullest chip's
``memory_stats()``).
"""
