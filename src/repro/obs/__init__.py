"""repro.obs — request-lifecycle tracing + metrics for serve/train.

Three pieces, all zero-dependency (stdlib + numpy):

* :class:`Tracer` (``obs.trace``) — request-lifecycle spans (submitted
  -> admitted -> prefilling -> decoding -> drained, plus drops) with
  host wall-clock timestamps AND device step counters, exported as
  Chrome trace-event JSON;
* :class:`Metrics` (``obs.metrics``) — counters, gauges and fixed
  log-bucket histograms, snapshotted to JSONL;
* instrumentation hooks in ``serve.engine`` (both batchers),
  ``serve.router`` (queue depth, rebalances), ``serve.pages`` (pool
  occupancy, prefix hits, COW) and the ``launch.serve`` /
  ``launch.train`` drivers (``--trace`` / ``--metrics-out``).

The device batcher's fused step always stamps each request's admission
and first-token steps (two int32 rows of its output, read with the
outputs); its ``admitted_at`` / ``first_at`` / ``done_at`` and an
attached Tracer are fed from those stamps, so the step and the host loop
are the same code with a tracer or without, and token streams are
bit-exact either way.  Beside these, the serve path marks itself for the
JAX profiler: ``jax.named_scope`` names in the fused step (``gate``,
``kv``, ``attention``, ``mla`` (latent attention's projections into and
out of the latent space), ``mlp`` / ``moe``, ``lm_head``, ``sample``) and
``serve.*`` host spans in ``DeviceContinuousBatcher.run``.
"""
from .metrics import Counter, Gauge, Histogram, Metrics
from .trace import RequestTrace, Tracer, step_time_interp

__all__ = ["Counter", "Gauge", "Histogram", "Metrics", "RequestTrace",
           "Tracer", "step_time_interp"]
