"""Observability: structured step tracing, unified metrics, profiling.

The cross-cutting layer (docs/guide/observability.md) that makes the
async training loop (training.py), the continuous-batching engine
(generation/engine.py) and the resilience subsystem visible while they
run:

* ``trace``    — sync-free host spans: always a ``jax.profiler``
  annotation (any capture, the device planes' clock), and a bounded ring
  -> Chrome/Perfetto JSON when one is configured;
* ``registry`` — process-wide counters/gauges/histograms -> Prometheus
  text;
* ``exporter`` — HTTP ``/metrics`` + ``/profile`` endpoint
  (``--metrics_port``);
* ``profiler`` — on-demand ``jax.profiler`` windows (SIGUSR2,
  ``/profile?steps=N`` on the trainer, ``/profile?ticks=N`` on the
  generation server);
* ``compiles`` — the program's own record of its XLA compilations (a
  time-stamped log by function: traced, lowered, compiled cold or
  loaded from the cache) and of its start-up phases;
* ``flops``    — config-derived flops/MFU math shared by driver, bench
  and registry;
* ``flight``   — per-request flight recorder: bounded event logs with an
  exact latency decomposition, served on ``/debug/requests`` and dumped
  by the watchdog.

Package-wide contract, enforced by the ``obs-no-sync`` graftcheck rule
(docs/guide/static-analysis.md): nothing in here may sync the device —
no ``jax.device_get``, no ``block_until_ready`` — because observability
must never perturb the overlap it measures (the PR-2
bitwise-identical-loss guarantee includes running with every instrument
on).  This docstring can name those calls only because the rule is
AST-based: prose is prose, a call is a finding.
"""

from megatron_llm_tpu.observability import flight, flops, registry, trace

__all__ = ["flight", "flops", "registry", "trace"]
