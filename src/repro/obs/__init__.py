"""repro.obs — lightweight structured telemetry for the repro stack.

Counters, point events, and timed spans with key/value attributes,
routed to an installed collector (in-memory for tests, JSONL file for
runs); a no-op when disabled.  Select a sink with the ``REPRO_OBS``
env var (``memory`` / ``jsonl:PATH`` / a bare path; unset = off) or
install one programmatically.

Instrumented layers and their event names (see README § Observability):

  kernel.resolve           one event per op dispatch: winning config
                           source (explicit/tuned/planned/default) and
                           the resolved (D, P, block_rows, arrangement)
  kernel.plan_memo.*       planner-memo hit/miss counters
  codegen.spec_memo.*      make_kernel_op classify/traffic memo counters
  tune.trial               one event per autotune candidate: config,
                           median seconds, planner predicted_bw, and
                           measured GiB/s from the spec's Traffic bytes
  tune.result              the sweep's winner (or the rehydrated hit)
  tune.cache.*             autotune-level cache hit/miss counters
  tunecache.*              entry-level hit/miss/sibling_fallback counters
  serve.step               per-token decode/prefill step: latency,
                           active slots, queue depth, request uids
  serve.request            per-request TTFT (queue_s +
                           first_token_wait_s) / tokens-per-second
  serve.run, serve.admit,  serving spans: one run(), a request into a
  serve.prefill,           slot (uid, slot, queue_s), its prompt (uid,
  serve.retire             tokens), its retirement (uid, n_tokens)
  serve.round,             per decode round (uids) and per step (phase):
  serve.dispatch,          no Event; their times add up in
  serve.sync,              engine.stats()["host"]
  serve.bookkeep
  analysis.pass            static verifier validated a (spec, config)
  analysis.violation       one event per static finding: rule id,
                           severity, locus, message
  analysis.rejected_candidates
                           planner sweep candidates dropped by the
                           static verifier (counter)

The full name table lives in README § Observability; the repo lint
(``tools/speclint.py --repo-lint``) checks every emitted name appears
there.
"""
from repro.obs.core import (Event, MemoryCollector, active_collector,
                            collect, counter, enabled, event, install,
                            span, uninstall)
from repro.obs.sinks import JsonlSink, configure_from_env, read_jsonl

__all__ = [
    "Event", "MemoryCollector", "JsonlSink",
    "enabled", "active_collector", "event", "counter", "span",
    "install", "uninstall", "collect", "configure_from_env", "read_jsonl",
]

# Honour $REPRO_OBS at import time: one env read; near-zero cost when
# unset (every later emit call is a single None check).
configure_from_env()
