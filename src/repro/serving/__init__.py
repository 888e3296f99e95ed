"""Multi-tenant serving runtime (registry → batcher → scheduler → service).

The deployment layer over the graph compiler: many compiled Programs —
the same model at several precisions, or different models — served
concurrently from one process, the way the paper's runtime-programmable
fabric runs mixed-precision networks without reconfiguration.

* :mod:`repro.serving.registry`  — model/precision registry: lazy compile,
  LRU eviction, content-addressed packed-weight sharing, and (with
  ``store=``) AOT artifact warm boot — zero recompiles on restart
  (:mod:`repro.compiler.artifact`).
* :mod:`repro.serving.batcher`   — request queue + dynamic micro-batcher
  with power-of-two padding buckets and backpressure.
* :mod:`repro.serving.scheduler` — MVU-slot admission in the cycle domain
  (cost model + BarrelController simulation, per-slot utilization).
* :mod:`repro.serving.service`   — the thread-driven front end:
  ``submit`` / ``submit_many`` / ``drain`` + the metrics snapshot.
* :mod:`repro.serving.lm_engine` — continuous-batching autoregressive LM
  decode: a persistent jitted loop over a ``batch_slots x max_len`` slot
  arena where requests join/leave at token boundaries; the scheduler is
  booked per decode step, not per request.

With ``n_banks > 1`` the service scales across a device mesh — one 8-slot
MVU bank per jax device (:mod:`repro.distributed.program_parallel`): the
scheduler books ``n_banks x 8`` slots, weight planes replicate once per
device, and micro-batches either load-balance across banks
(``placement="banked"``) or split evenly over all of them
(``placement="sharded"``).

Observability (:mod:`repro.obs`): the service threads one
:class:`~repro.obs.metrics.MetricsRegistry` and one
:class:`~repro.obs.tracing.Tracer` through every component it constructs —
every legacy ``metrics()``/``stats()`` dict is registry-backed, each
request carries a trace context (its queue span, linked by ``batch_id``
to its micro-batch's form/schedule/execute/finalize spans, recorded once
per batch in wall-ns *and* virtual MVU cycles), and the scheduler keeps
per-bank HPM counter files (per-hart busy/xfer/issue/stall with
per-tag/per-precision attribution). Export via
:func:`repro.obs.write_chrome_trace` (Perfetto) and
:func:`repro.obs.prometheus_text` over ``service.registries()``.
"""

from repro.serving.batcher import (DynamicBatcher, MicroBatch, QueueFull,
                                   Request)
from repro.serving.lm_engine import (ContinuousLMEngine, decode_cost_stream,
                                     supports_continuous)
from repro.serving.registry import ModelKey, ModelRegistry, precision_label
from repro.serving.scheduler import Admission, SlotScheduler
from repro.serving.service import InferenceService

__all__ = ["ModelKey", "ModelRegistry", "precision_label", "DynamicBatcher",
           "MicroBatch", "Request", "QueueFull", "SlotScheduler",
           "Admission", "InferenceService", "ContinuousLMEngine",
           "supports_continuous", "decode_cost_stream"]
