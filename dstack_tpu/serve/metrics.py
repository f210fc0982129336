"""Serve-engine metric families (obs registry factory).

One construction point for every ``dtpu_serve_*`` series, used by:

- :class:`dstack_tpu.serve.engine.InferenceEngine` — records TTFT,
  per-step decode latency, TPOT, token counters,
  and prefix-cache counters at the source (the engine), so whatever
  scrapes ``/metrics`` (the benchmark's per-layer readers under
  ``benchmark/readers/``, the shim relay) reads ONE set of numbers
  instead of keeping stopwatches of its own.
- ``serve/openai_server.py`` — sets the scheduler-level gauges
  (queue depth, batch occupancy, KV utilization) and serves the
  rendered page from ``/metrics`` for the shim relay to scrape.
- ``tools/check_metrics_docs.py`` — enumerates the family names to
  hold docs/reference/server.md to account.

Import-light on purpose (no jax): the docs checker and unit tests
instantiate the registry without an accelerator runtime.
"""

from dstack_tpu.obs import (
    LATENCY_BUCKETS_S,
    Registry,
    SHORT_LATENCY_BUCKETS_S,
)


def new_serve_registry() -> Registry:
    """Registry pre-populated with every serve metric family."""
    r = Registry()
    # request lifecycle
    r.counter(
        "dtpu_serve_requests_total", "Requests admitted to the scheduler"
    )
    r.counter(
        "dtpu_serve_tokens_generated_total", "Tokens sampled across all slots"
    )
    r.counter(
        "dtpu_serve_decode_steps_total", "Engine step() calls"
    )
    # latency distributions
    r.histogram(
        "dtpu_serve_ttft_seconds",
        "Slot-admission-to-first-token latency (chunked prefill incl. "
        "any prefix-cache reuse; excludes scheduler queue wait — add "
        "dtpu_serve_queue_wait_seconds for the client-observed TTFT)",
        buckets=LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_queue_wait_seconds",
        "Submit-to-slot-admission wait in the scheduler queue (the "
        "saturation component of client-observed TTFT)",
        buckets=LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_decode_step_seconds",
        "Wall time of one engine step (a turbo macro-step counts once)",
        buckets=LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_tpot_seconds",
        "Time per output token: step wall time / tokens emitted",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    # host phases of the serve loop, each timed by its caller around
    # the interval that obs/profiling.span puts on a capture's clock
    # (unlabelled: scrapers that sum label sets read them as they are)
    r.histogram(
        "dtpu_serve_host_gap_seconds",
        "Engine-idle gap: return of one engine call (step or prefill "
        "wave) to the start of the next while requests hold slots — "
        "the device has no work queued (parked-idle waits excluded)",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_tick_host_seconds",
        "Scheduler tick's own host code: deadline sweep + admission "
        "walk + token hand-over (engine calls excluded), one "
        "observation per tick",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_detokenize_seconds",
        "Streaming handler re-decode of a request's ids into emittable "
        "text, one observation per token consumed (plus the final "
        "flush)",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_stream_write_seconds",
        "Streaming handler chunk build + json.dumps + socket write, "
        "one observation per SSE chunk",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    # the decode cycle's host part by name (PERF.md §3): an emitting
    # engine.step() call is enqueue + wait + finish (shared clock reads,
    # so the three sums add up to the calls' wall time) ...
    r.histogram(
        "dtpu_serve_step_enqueue_seconds",
        "Emitting engine step's own host time up to and between its "
        "blocking fetches: fault hooks, path choice, decode-state "
        "uploads, the jit calls (the call's wall time minus the two "
        "below), one observation per call",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_step_wait_seconds",
        "Emitting engine step's time inside its blocking device-to-host "
        "fetches (the device runs or the transfer does, the worker is "
        "parked), summed per call",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_step_finish_seconds",
        "Emitting engine step's host time from the return of its last "
        "fetch to the return of step(): routing counts, token "
        "bookkeeping, counters, histograms, the flight record",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_prefill_host_seconds",
        "One prefill_wave() call's wall time minus its time inside the "
        "blocking fetches (a non-final chunk does not sync: all of it "
        "is enqueue)",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    # ... and dtpu_serve_host_gap_seconds is loop_return + tick_host +
    # loop_yield + worker_start + a few lines of the scheduler's loop
    # (counted only where the gap is: a park drops what was noted)
    r.histogram(
        "dtpu_serve_loop_return_seconds",
        "Return of an engine call on its worker thread to the "
        "scheduler's coroutine running again on the event loop",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_loop_yield_seconds",
        "Return of a token hand-over to the first line of the "
        "scheduler's next tick: event-loop time given away with no "
        "engine call in flight (about zero: the stream handlers' turn "
        "lies inside the next call's await)",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_worker_start_seconds",
        "Scheduler handing an engine call to a worker thread to the "
        "call's first line running there",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    r.histogram(
        "dtpu_serve_first_delta_lag_seconds",
        "Scheduler's token hand-over to a streaming request to the end "
        "of the handler's next delta write, one observation per "
        "request per hand-over (not per token)",
        buckets=SHORT_LATENCY_BUCKETS_S,
    )
    # where the stream handlers' turn falls: inside an engine call's
    # await (the device computes) or outside one. inc(0): the series
    # exist from boot, so the share of the two is defined
    r.counter(
        "dtpu_serve_stream_tokens_total",
        "Tokens the streaming chat handlers took off their queues "
        "(detokenized and, where a delta came of it, written)",
    ).inc(0)
    r.counter(
        "dtpu_serve_stream_tokens_overlapped_total",
        "Of those, the tokens taken while an engine call was in "
        "flight on the worker thread",
    ).inc(0)
    # prefill dispatch accounting: the packed multi-slot prefill packs
    # up to prefill_pack concurrent prompt chunks into one forward —
    # dispatches per burst is the TTFT-under-load lever these observe
    r.counter(
        "dtpu_serve_prefill_dispatches_total",
        "Prefill forward dispatches (a packed wave counts once)",
    )
    r.histogram(
        "dtpu_serve_prefill_pack_rows",
        "Prompt chunk rows per prefill dispatch (1 = serial; >1 = "
        "packed multi-slot prefill)",
        buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0),
    )
    # engine/scheduler state gauges
    r.gauge("dtpu_serve_queue_depth", "Requests waiting for a slot")
    r.gauge("dtpu_serve_active_slots", "Slots currently decoding")
    r.gauge("dtpu_serve_max_slots", "Configured slot count (max_batch)")
    r.gauge(
        "dtpu_serve_batch_occupancy_ratio",
        "active_slots / max_slots (continuous-batching fill)",
    )
    r.gauge(
        "dtpu_serve_kv_cache_utilization_ratio",
        "Cached tokens across live slots / (max_batch * max_seq)",
    )
    r.counter(
        "dtpu_serve_request_errors_total",
        "Requests this replica failed server-side (engine/prefill/"
        "admission errors, watchdog aborts, deadline expiries) — "
        "behind the router these streams fail over or resume, so "
        "clients may see none of them; the live SLO engine's "
        "error-rate objective burns on this, which is exactly how a "
        "soft-failing replica gets caught before its breaker would "
        "(obs/slo.py). Honest 503 sheds are NOT counted",
    )
    # request lifecycle hardening: deadlines, watchdog, stream resume
    r.counter(
        "dtpu_serve_deadline_expired_total",
        "Requests aborted because their per-request deadline "
        "(X-DTPU-Deadline / DTPU_REQUEST_DEADLINE_DEFAULT) expired — "
        "queued or in a slot; an aborted slot frees its KV immediately",
    )
    r.counter(
        "dtpu_serve_watchdog_aborts_total",
        "Engine-watchdog trips: a step() dispatch exceeded "
        "DTPU_ENGINE_WATCHDOG_SECONDS and was abandoned (the wedged "
        "slot — or, unattributable, the whole batch — was aborted)",
    )
    r.counter(
        "dtpu_serve_resumed_requests_total",
        "Continuations accepted via the router's mid-stream-failover "
        "resume extension (prompt re-prefilled with already-delivered "
        "tokens; admission charge stays on the original leg)",
    )
    # XLA compile accounting (obs/flight.py watch_jit wrappers): the
    # `fn` label is the bounded enum of engine jit sites — decode/
    # verify/sample/argmax/advance_state/logprobs/mark_seen/
    # mark_prompt/skip_key plus the memoized grids chunk/packed/turbo/
    # copy — never a request-derived value
    r.counter(
        "dtpu_serve_compiles_total",
        "XLA trace/compile events per engine jit site (first call of a "
        "new shape/bucket variant; the causing bucket key rides the "
        "flight ring's compile records)",
        labelnames=("fn",),
    )
    r.histogram(
        "dtpu_serve_compile_seconds",
        "Wall time of compile-triggering calls per jit site (trace + "
        "compile + first execution — the cost the triggering request "
        "actually paid)",
        labelnames=("fn",),
        buckets=LATENCY_BUCKETS_S,
    )
    r.counter(
        "dtpu_serve_recompiles_total",
        "Steady-state recompiles: compile events observed AFTER "
        "warmup declared the engine warm — each one is a live "
        "TTFT/TPOT stall some request paid: an unwarmed grid cell "
        "(warmup coverage gap) or a broken power-of-two bucketing "
        "contract (the runtime complement of lint rule DTPU003). "
        "Identical steady traffic must never advance this (pinned by "
        "the two-pass regression test)",
        labelnames=("fn",),
    )
    r.counter(
        "dtpu_serve_warmup_gap_compiles_total",
        "Steady-state compiles of a variant ABSENT from the "
        "boot-compile manifest (the per-fn compile keys warmup "
        "visited): warmup never covered that bucket, so a live "
        "request paid its first-ever trace. The subset of "
        "dtpu_serve_recompiles_total that indicts warmup coverage "
        "rather than cache churn (obs/boot.py manifest helpers; "
        "gated by the two-pass recompile test)",
        labelnames=("fn",),
    )
    r.gauge(
        "dtpu_serve_compile_cache_entries",
        "Entries in the engine's memoized jit grids (fn = chunk/"
        "packed/turbo/copy) — the compile-cache footprint the "
        "log2-bucket contracts bound",
        labelnames=("fn",),
    )
    r.counter(
        "dtpu_serve_postmortems_total",
        "Flight post-mortem snapshots captured FOR THIS ENGINE "
        "(watchdog aborts, engine/prefill errors, deadline "
        "batch-aborts) — the per-replica signal /health embeds; the "
        "process-wide ring count is dtpu_flight_postmortems_total",
    )
    # device-memory watermarks (best-effort jax memory_stats; absent —
    # not zero — on backends without stats, e.g. CPU jaxlib)
    r.gauge(
        "dtpu_serve_device_memory_bytes_in_use",
        "Device HBM bytes in use, summed across local devices "
        "(best-effort jax memory_stats; series absent when the "
        "backend exposes no stats)",
    )
    r.gauge(
        "dtpu_serve_device_memory_peak_bytes",
        "Running peak of device HBM bytes in use since engine start "
        "(high-water mark across polls; series absent when the "
        "backend exposes no stats)",
    )
    # prefix cache
    r.counter(
        "dtpu_serve_prefix_hits_total",
        "Requests that reused a cached chunk-aligned prompt prefix",
    )
    r.gauge(
        "dtpu_serve_prefix_slots",
        "Prefix-registry slots currently holding a reusable prompt "
        "(also reported on /health as prefix_slots for the router's "
        "cache-aware affinity score)",
    )
    r.counter(
        "dtpu_serve_prefix_tokens_reused_total",
        "Prompt tokens skipped via prefix-cache reuse",
    )
    # the sampler: how often a live slot's top-k / top-p / min-p made a
    # call pay the full-vocabulary sort (engine.sample skips the
    # filters on every other call). inc(0): the series exist from boot,
    # so a scrape reads 0 and the share of the two is defined
    r.counter(
        "dtpu_serve_sample_calls_total",
        "Calls of the sampler (a decode step of a batch that is not "
        "all plain-greedy, and every request's first token)",
    ).inc(0)
    r.counter(
        "dtpu_serve_sample_filtered_calls_total",
        "Of those, the calls on which a live slot had set top_k, "
        "top_p or min_p, so that the sampler sorted the vocabulary "
        "(host-side, from the slots' parameters)",
    ).inc(0)
    # the full layers' decode attention: the key rows it read beside the
    # rows the slots reserve (series exist from boot; equal for a program
    # that reads whole rows)
    r.counter(
        "dtpu_serve_decode_keys_read_total",
        "Key rows the full-attention layers' decode attention read: "
        "slots x key blocks up to the longest live context x layers a "
        "token step where a latent program follows the contexts, each "
        "emitting slot's own key blocks x layers where a grouped-query "
        "one does (ops/flash_decode), slots x max_seq x layers where it "
        "reads whole rows (host-side, from positions)",
    ).inc(0)
    r.counter(
        "dtpu_serve_decode_keys_reserved_total",
        "Key rows those layers reserve: slots x max_seq x layers a "
        "token step, the denominator of the read share",
    ).inc(0)
    # layer groups: the sparse indexer and a chip's share of the experts
    # (series stay 0 for a model that has neither)
    r.counter(
        "dtpu_serve_indexer_keys_selected_total",
        "Keys the sparse indexer let decoded tokens attend to, summed "
        "over full-attention layers: min(context, index_topk) a token "
        "a layer (host-side, from positions)",
    )
    r.counter(
        "dtpu_serve_indexer_keys_in_context_total",
        "Causal keys in the context of those tokens, summed the same "
        "way: the denominator of the selected share",
    )
    r.counter(
        "dtpu_serve_moe_picks_held_total",
        "Router picks that landed on an expert this chip holds "
        "(experts_held), over real tokens of prefill and decode, "
        "summed on the device and fetched with a step's tokens",
    )
    r.counter(
        "dtpu_serve_moe_tokens_routed_total",
        "Token x expert-layer routings behind those picks (a model "
        "holding every expert counts none)",
    )
    # a chip's share of the experts: of the experts it holds, how many a
    # routed layer call read the weights of (moe.reads_picked_experts)
    r.counter(
        "dtpu_serve_moe_experts_read_total",
        "Experts whose weights a routed layer call read, summed over "
        "the calls of prefill, decode and verify: the distinct held "
        "experts some real token picked where the call reads only "
        "those, every held expert where it runs the capacity form",
    ).inc(0)
    r.counter(
        "dtpu_serve_moe_experts_held_total",
        "Experts held (experts_held) over the same calls: the "
        "denominator of the share of held experts read",
    ).inc(0)
    # a router with identity ("zero-computation") experts among its
    # outputs: how much of the routing costs no weights
    r.counter(
        "dtpu_serve_moe_picks_zero_total",
        "Router picks that fell on an identity expert (zero_experts: "
        "the token itself times its gate, no weights), over real "
        "tokens of prefill and decode, summed on the device with the "
        "held picks",
    ).inc(0)
    r.counter(
        "dtpu_serve_moe_picks_total",
        "All router picks of those tokens (routings x experts_per_token)"
        ": the denominator of the zero picks' share; 0 for a router "
        "without identity experts",
    ).inc(0)
    # window layers beside full ones (either family): what their mask
    # lets a decoded token see of its context, and what their ring
    # takes of the cache
    r.counter(
        "dtpu_serve_window_keys_visible_total",
        "Keys the window layers' mask let decoded tokens see, summed "
        "over window layers: min(context, sliding_window) a token a "
        "layer (host-side, from positions)",
    )
    r.counter(
        "dtpu_serve_window_keys_in_context_total",
        "Causal keys in the context of those tokens, summed the same "
        "way: the denominator of the visible share",
    )
    r.gauge(
        "dtpu_serve_kv_cache_bytes",
        "Bytes of the K/V (or latent) cache buffers allocated at "
        "engine construction, all layer kinds",
    )
    r.gauge(
        "dtpu_serve_kv_window_pool_percent",
        "Of those bytes, the share held by the window layers' rings "
        "(sized by the window, not by max_seq); 0 for a model of one "
        "kind of layer",
    )
    # layers that hold a slot's past whole (linear-attention layers: a
    # recurrent state and a convolution tail; gated short-convolution
    # layers: a tail) beside the rows
    r.gauge(
        "dtpu_serve_state_cache_percent",
        "Of the cache's bytes, the share without a token axis: the "
        "linear and mamba layers' recurrent states and the linear, conv "
        "and mamba layers' convolution tails (sized by the widths, not "
        "by max_seq); 0 for a model without such layers",
    ).set(0)
    # a model whose upper layers keep nothing (gmu and cross layers
    # over one K/V leaf): the prompt positions its two halves computed
    # (two counters and no label: the benchmark's scrape sums label sets)
    r.counter(
        "dtpu_serve_prefill_lower_rows_total",
        "Prompt positions that the layers which keep a state, a ring or "
        "rows computed in prefill, on a model that also has layers "
        "which keep nothing; 0 for any other model",
    ).inc(0)
    r.counter(
        "dtpu_serve_prefill_upper_rows_total",
        "Prompt positions that the layers which keep nothing (gmu, "
        "cross) computed in prefill: a prompt's last position alone "
        "needs them; today every position goes through them",
    ).inc(0)
    r.counter(
        "dtpu_serve_state_resets_total",
        "Slots started from zeros: requests that started on a model "
        "with linear or conv layers, each at position 0 from no state "
        "and no tail",
    ).inc(0)
    # group-limited routing with a chip's share of whole groups
    r.counter(
        "dtpu_serve_moe_tokens_group_hit_total",
        "Routed token-layers (as dtpu_serve_moe_tokens_routed_total) "
        "one of whose eligible expert groups is held here: the tokens "
        "an expert-parallel layer would send this chip; summed on the "
        "device with the held picks, 0 without router_groups",
    ).inc(0)
    return r
