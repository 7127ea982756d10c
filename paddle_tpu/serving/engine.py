"""Shape-stable serving engine: bucketed prefill + slot KV cache +
continuous-batching decode.

Legacy generate() compiles one monolithic prefill+scan program per exact
(batch, prompt_len, max_new_tokens, sampling-config) tuple and always burns
max_new_tokens scan steps. Under mixed traffic that is a recompile per
shape class and wasted steps past every early EOS. The engine splits
generation into two shape-stable compiled artifacts instead (the
resident-program philosophy of MPK, arxiv 2512.22219):

- **bucketed prefill**, one executable per prompt-bucket rung: the prompt
  is right-padded to the rung, run through the model with causal masking,
  and its K/V scattered into this request's row of the slot cache. The
  true prompt length, target slot, sampling params, and seed are all
  traced, so a whole traffic distribution shares O(#rungs) executables.
- **a single-token decode step**, ONE executable total: operates on the
  fixed donated slot KV cache (kv_state.py: one object built from what the
  model declares it keeps a layer and from `kv_layout`, over which every
  program here is written once) with per-slot write offsets, per-slot sampling params (traced — mixed greedy/top-k/top-p
  share the program), per-slot EOS/budget masks, and per-slot RNG streams.

A model that generates by diffusion over blocks (`model.generation`, a
`BlockDiffusion`) gets two others of the same form: a **block prefill** a
rung, which runs the prompt's whole blocks and draws nothing, and a **block
step** (`_build_block_decode`): the same scan over the donated slot cache
whose every forward covers a block of B positions a slot and yields 0 or B
tokens a slot (serving/diffusion.py). Budgets, run-ahead and every counter
of tokens count tokens either way.

On top sits continuous batching: finished sequences retire their slot
mid-flight and queued requests are prefilled into free slots between decode
steps — the decode loop itself never recompiles and never runs a step for
work that is already done (only for idle slots while ANY slot is live,
which is the slot-occupancy metric the telemetry records). While no slot
can come free, the loop keeps one decode chunk enqueued behind the one it
is reading (`_may_run_ahead`), so the device does not wait for the host.

CPU-demonstrable (tools/serve_bench.py); the same two executables are what
a TPU deployment keeps resident.
"""
from __future__ import annotations

import itertools
import signal
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

from ..core import flags as _flags
from ..core.exec_registry import ExecutableRegistry
from ..observability import exec_introspect as _obs_exec
from ..observability import exporter as _obs_exporter
from ..observability import flight_recorder as _obs_flight
from ..observability import metrics as _obs_metrics
from ..observability import tracer as _obs_tracer
from . import kv_state as _kvs
from .diffusion import remasking_id
from ..core.bucketing import DEFAULT_LADDER, bucket_for, clip_ladder

_NO_EOS = -1

# slot-occupancy fractions live in (0, 1]: linear buckets, not the default
# log-spaced latency boundaries
_OCCUPANCY_BUCKETS = tuple(round(0.1 * i, 1) for i in range(1, 11))


def _split(args, *counts):
    """A program's positional arguments after its weights -> each cache's
    own (`counts[i]` of them, in order: `n_args` of kv_state.py), then the
    program's."""
    out, i = [], 0
    for n in counts:
        out.append(tuple(args[i:i + n]))
        i += n
    return (*out, args[i:])


class Request:
    """One generation request and its lifecycle record."""

    _ids = itertools.count()

    def __init__(self, prompt_ids, max_new_tokens, temperature, top_k, top_p,
                 eos_token_id, seed, trace_ctx=None, tenant=None,
                 speculate_k=0, denoising_steps=None, remasking=None,
                 confidence_threshold=None, record_blocks=False):
        import numpy as np

        self.id = next(Request._ids)
        # multi-tenant attribution (serving/loadgen.py scenarios): carried
        # into the serve_request sink record so per-tenant latency/goodput
        # can be cut offline; None = untagged, zero extra cost
        self.tenant = tenant if tenant is None else str(tenant)
        # fleet trace identity (observability.fleet.TraceContext or any
        # object with span_args()): set by the ReplicaRouter so engine-side
        # spans carry the request id + the placement span as parent_span
        self.trace_ctx = trace_ctx
        self.prompt_ids = np.asarray(prompt_ids, np.int64).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token_id = (int(eos_token_id) if eos_token_id is not None
                             else None)
        self.seed = int(seed)
        # speculative decoding opt-in: > 0 asks the engine to draft this
        # many tokens per verify window (snapped up to the engine's
        # spec_ladder rung; requires a draft model). Proposed/accepted/
        # bonus accumulate across the request's verify dispatches.
        self.speculate_k = int(speculate_k)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_bonus = 0
        # generation by diffusion over blocks (serving/diffusion.py): how
        # many forwards a block's positions are spread over, how positions
        # are chosen, the dynamic schedule's threshold (None: the model's).
        # `block_states`, where asked for, is the request's blocks forward by
        # forward: (offset, the block after the forward, whether it was
        # committed, the tokens drawn, their confidences)
        self.denoising_steps = denoising_steps
        self.remasking = remasking
        self.confidence_threshold = confidence_threshold
        self.block_states: Optional[list] = [] if record_blocks else None
        self.given_in_block = 0          # prompt tokens that open a block
        self.block_offset = 0            # positions held before the block
        self.tokens: List[int] = []      # generated tokens (incl. eos if hit)
        self.prefix_hit = False          # paged: >= 1 page matched the trie
        self.shared_tokens = 0           # paged: prompt tokens served from
        self.tail_bucket: Optional[int] = None  # shared pages (no prefill)
        self.bucket: Optional[int] = None
        self.slot: Optional[int] = None
        self.queue_depth_at_submit = 0
        self.submit_ts: Optional[float] = None
        self.admit_ts: Optional[float] = None
        self.first_token_ts: Optional[float] = None
        self.done_ts: Optional[float] = None
        self.finish_reason: Optional[str] = None  # "eos" | "length"
        # terminal disposition, set when the request leaves the engine:
        # "ok" | "eos" | "length" (normal), "drained" (drain timeout cut
        # it short), "error" (prefill/decode raised) — the error-rate
        # SLI's numerator/denominator
        self.outcome: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.done_ts is not None

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_ts is None or self.submit_ts is None:
            return None
        return self.first_token_ts - self.submit_ts

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.admit_ts is None or self.submit_ts is None:
            return None
        return self.admit_ts - self.submit_ts

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time-per-output-token after the first (None until done or
        when only one token was generated)."""
        if (self.done_ts is None or self.first_token_ts is None
                or len(self.tokens) < 2):
            return None
        return (self.done_ts - self.first_token_ts) / (len(self.tokens) - 1)

    def trace_args(self, **kw) -> dict:
        """Span-args dict for this request's trace events: local id plus
        the propagated fleet request id / parent placement span (if any)."""
        out = {"request": self.id}
        if self.trace_ctx is not None:
            out.update(self.trace_ctx.span_args())
        out.update(kw)
        return out

    def output_ids(self):
        """[prompt + generated] (no post-EOS padding; pad with eos to
        compare against legacy generate() fixed-length output)."""
        import numpy as np

        return np.concatenate(
            [self.prompt_ids, np.asarray(self.tokens, np.int64)])

    def __repr__(self):
        return (f"Request(id={self.id}, prompt={len(self.prompt_ids)}, "
                f"new={len(self.tokens)}/{self.max_new_tokens}, "
                f"done={self.done})")


class ServingEngine:
    """Continuous-batching decoder serving over a slot-based KV cache.

    model: a causal LM that says what the engine needs of it and nothing of
    its architecture (GPTForPretraining, AfmoeForCausalLM,
    OlmoHybridForCausalLM, DeepseekV2ForCausalLM, SdarForCausalLM): `config`
    (`vocab_size`, `max_seq_len`), `serving_backbone()` (the layer called
    with `(ids, caches=...)` and its prefix in `state_dict`),
    `kv_cache_spec(max_seq_len)` (what each layer keeps a slot, rows of keys
    and values or a recurrent state: nn/kv_cache.py, kv_state.py),
    `_head_logits(h)`, `serving_step_stats` (what a decode step reports
    beside its tokens) and, where it does not generate one token after
    another, `generation` (a `BlockDiffusion`: the decode program is then a
    step of a block of positions a slot, `_build_block_decode`). Eval mode
    is forced. slot_count fixes the
    decode batch; ladder the prefill rungs (clipped to what fits
    max_seq_len with max_new_cap headroom). Weights are snapshotted (and
    pre-cast to the active AMP compute dtype) at construction — call
    refresh_params() after updating the model.

    sink: StepTelemetry-style sink (write(dict)/close()) receiving one
    "serve_request" record per completed request (TTFT, tokens/s, slot,
    bucket, queue depth) and one "serve_step" record per decode step (slot
    occupancy, queue depth). None = no telemetry, no overhead.

    Single-driver: submit() is thread-safe, step()/run() must be called
    from one thread.
    """

    @_obs_tracer.in_boundary("serve.engine.init")
    def __init__(self, model, slot_count: int = 4,
                 ladder: Sequence[int] = DEFAULT_LADDER,
                 max_seq_len: Optional[int] = None,
                 max_new_cap: int = 64, steps_per_dispatch: int = 8,
                 sink=None, kv_layout: str = "contiguous",
                 kv_page_tokens: Optional[int] = None,
                 kv_num_pages: Optional[int] = None,
                 kv_cache_dtype: Optional[str] = None,
                 draft_model=None, spec_ladder: Sequence[int] = (4,)):
        import jax.numpy as jnp
        import numpy as np

        cfg = model.config
        self.model = model
        model.eval()
        # a model that generates by diffusion over blocks says so
        # (nn/kv_cache.py `BlockDiffusion`): the decode program is then
        # `_build_block_decode`, a step of B positions a slot
        self._diffusion = getattr(model, "generation", None)
        if self._diffusion is not None:
            if draft_model is not None:
                raise ValueError(
                    "speculative decoding cannot run this model: it "
                    "generates by diffusion over blocks, a step yields 0 or "
                    f"{self._diffusion.block_length} tokens a slot, and the "
                    "verify program accepts a prefix of single tokens; "
                    "construct the engine without draft_model")
            if kv_layout == "paged":
                raise ValueError(
                    "kv_layout='paged' cannot hold this model: it generates "
                    "by diffusion over blocks, whose rows are rewritten by "
                    "every forward over a block, and a prefix shared across "
                    "requests may be cut at a block's edge only; neither is "
                    "written for the page pool or the prefix cache, use "
                    "'contiguous'")
        # speculative decoding (opt-in per request via submit(speculate_k=)):
        # a small draft GPT proposes k tokens, one shape-stable verify
        # dispatch scores all k+1 positions through the target. The draft
        # shares the target's tokenizer space — vocab agreement is a hard
        # precondition of token-level acceptance.
        self.draft_model = draft_model
        if draft_model is not None:
            draft_model.eval()
            if draft_model.config.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.config.vocab_size} != target "
                    f"vocab {cfg.vocab_size}: speculative acceptance "
                    "compares token ids, the vocabularies must agree")
            self.spec_ladder = tuple(sorted(int(k) for k in spec_ladder))
            if not self.spec_ladder or min(self.spec_ladder) < 1:
                raise ValueError(
                    f"spec_ladder must be non-empty positive rungs, got "
                    f"{spec_ladder!r}")
        else:
            self.spec_ladder = ()
        self.slot_count = int(slot_count)
        if self.slot_count < 1:
            raise ValueError(f"slot_count must be >= 1, got {slot_count}")
        self.max_seq_len = int(min(max_seq_len or cfg.max_seq_len,
                                   cfg.max_seq_len))
        self.max_new_cap = int(max_new_cap)
        if self.max_new_cap < 1 or self.max_new_cap >= self.max_seq_len:
            raise ValueError(
                f"max_new_cap {max_new_cap} must be in [1, max_seq_len)")
        self.ladder = clip_ladder(ladder, self.max_seq_len,
                                  reserve=self.max_new_cap)
        if self._diffusion is not None:
            B = self._diffusion.block_length
            if self.max_seq_len % B or any(r % B for r in self.ladder):
                raise ValueError(
                    f"max_seq_len {self.max_seq_len} and the ladder "
                    f"{self.ladder} must be multiples of the model's block "
                    f"length {B}: a prefill's pad starts on a block's edge")
        # decode steps fused into one dispatch (inner lax.scan): divides the
        # per-step host round-trip by N at the cost of (a) retired slots
        # idling masked until the chunk ends (<= N-1 wasted slot-steps per
        # retirement) and (b) admissions landing on chunk boundaries. Still
        # ONE decode executable; N is static in its key.
        self.steps_per_dispatch = max(1, int(steps_per_dispatch))
        self.sink = sink
        # PADDLE_TPU_METRICS_PORT / PADDLE_TPU_FLIGHT_DIR opt-ins: one
        # getenv each when unset, zero per-step cost while off
        _obs_exporter.ensure_started_from_env()
        _obs_flight.ensure_from_env()

        self._lock = threading.Lock()
        self._queue: deque[Request] = deque()
        self._completed: List[Request] = []
        self._steps = 0
        # what step() hands the serve_step sink record: the admission's
        # span times, and where the last decode fetch ended (host_gap_ms)
        self._admit_ms = None
        self._prefill_ms = []
        self._fetch_end = None
        # the decode loop's device side (`_decode_step`): the chunk that is
        # enqueued and not yet fetched, the carry the last enqueued chunk
        # returned (None once a seat has changed the host's copy) and the
        # per-slot constants as sent at the last seat; and how many decode
        # dispatches there were, how many of them enqueued ahead of a fetch
        self._inflight = None
        self._carry = None
        self._consts = None
        self._decode_dispatches = 0
        self._decode_ahead = 0
        # elastic drain state (distributed/membership.py protocol): once
        # draining, submit() refuses and _admit() stops pulling the queue —
        # active slots run to completion, then the replica retires
        self._draining = False
        self._replica_agent = None
        self._prev_sigterm = None
        # set by ReplicaRouter (or the owner): when non-None, _finish
        # additionally publishes serve.replica.<name>.* metrics — the
        # per-replica namespace the SLO self-healing hooks key on
        self.replica_name: Optional[str] = None

        self.refresh_params()

        S, T = self.slot_count, self.max_seq_len
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(
                f"kv_layout must be 'contiguous' or 'paged', got {kv_layout!r}")
        self.kv_layout = kv_layout
        spec = _kvs.spec_of(model, T)
        windows = _kvs.window_layers(spec)
        if windows and kv_layout == "paged":
            raise ValueError(
                f"kv_layout='paged' cannot hold this model: layers {windows} "
                "keep a window of rows, and kv_pages.py has one page table "
                "for all layers and no ring of pages; use 'contiguous'")
        if windows and draft_model is not None:
            raise ValueError(
                f"speculative decoding cannot run this model: layers "
                f"{windows} keep a window of rows as a ring, and the verify "
                "program rewinds rejected rows by offset, which a ring "
                "overwrites; construct the engine without draft_model")
        if draft_model is not None:
            _kvs.refuse_state_layers(
                spec, "speculative decoding",
                "the verify program rewinds rejected positions by offset, "
                "which a state cannot be; construct the engine without "
                "draft_model")
            _kvs.refuse_latent_layers(
                spec, "speculative decoding",
                "a verify window through the absorbed form, with rejected "
                "rows rewound by offset, is not written or held to the "
                "reference yet; construct the engine without draft_model")
        # the slot cache (kv_state.py): ONE object knows where a slot's rows
        # live; the programs below are written over it. Paged: per-layer
        # page pools + one page table traced as a gather index, and the
        # radix prefix cache sharing whole prompt pages across requests
        # (kv_pages.py). Shapes stay static either way, so the executables
        # and their donation are the same design.
        if kv_layout == "paged":
            from .kv_pages import PagedSlotCache

            self._kv = PagedSlotCache(
                spec, S, T, self._cache_dtype,
                int(kv_page_tokens if kv_page_tokens is not None
                    else _flags.flag("kv_page_tokens")),
                kv_num_pages,
                (kv_cache_dtype if kv_cache_dtype is not None
                 else _flags.flag("kv_cache_dtype")))
        else:
            self._kv = _kvs.SlotCache(spec, S, T, self._cache_dtype)

        # the draft's cache is always contiguous (draft rows rewind by
        # offset alone — rejected rows go stale-but-inert under the causal
        # mask, so the draft never needs page bookkeeping even when the
        # target cache is paged)
        self._dkv = None
        if draft_model is not None:
            dspec = _kvs.spec_of(draft_model, T)
            if _kvs.window_layers(dspec):
                raise ValueError("a draft model with window layers cannot "
                                 "rewind its cache by offset")
            _kvs.refuse_state_layers(
                dspec, "a draft model's cache",
                "the verify program rewinds the draft's rejected positions "
                "by offset, which a state cannot be")
            _kvs.refuse_latent_layers(
                dspec, "a draft model's cache",
                "a draft that decodes through the absorbed form beside the "
                "verify program is not written or held to the reference yet")
            self._dkv = _kvs.SlotCache(dspec, S, T, self._cache_dtype)

        # host-side per-slot state (tiny arrays; sent to the device after a
        # seat, between seats the decode program's own outputs go back in)
        self._offsets = np.zeros(S, np.int32)
        self._last_tok = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        self._temps = np.zeros(S, np.float32)
        self._topk = np.zeros(S, np.int32)
        self._topp = np.ones(S, np.float32)
        self._eos = np.full(S, _NO_EOS, np.int32)
        self._remaining = np.zeros(S, np.int32)
        self._seeds = np.zeros(S, np.int32)
        # per-slot speculative window rung (0 = plain decode for this slot);
        # mixed spec/non-spec slots share one verify dispatch — non-spec
        # rows run it as a 1-wide window, emitting exactly the decode token
        self._spec_k = np.zeros(S, np.int32)
        self._slot_req: List[Optional[Request]] = [None] * S
        # the decode program's carry and per-slot constants as the host's
        # arrays, in the program's order, and what it returns of each fused
        # step after the carry, by the names `_emit_decoded` /
        # `_emit_block_decoded` take them under ([n_inner, S] each; a block
        # step's blocks, draws and confidences [n_inner, S, B])
        self._carry_names = ("_offsets", "_last_tok", "_active", "_remaining")
        self._const_names = ("_temps", "_topk", "_topp", "_eos", "_seeds")
        self._out_names = ("toks", "was_active", "hits")
        # block diffusion: a slot's block, the forwards it has had, the
        # given tokens that open it, and the request's schedule
        if self._diffusion is not None:
            self._carry_names = ("_offsets", "_block", "_block_k", "_given",
                                 "_active", "_remaining")
            self._const_names += ("_dsteps", "_remask", "_thresh")
            self._out_names = ("blocks", "was_active", "commits", "unmasked",
                               "draws", "confs")
            B = self._diffusion.block_length
            self._block = np.full((S, B), self._diffusion.mask_token_id,
                                  np.int32)
            self._block_k = np.zeros(S, np.int32)
            self._given = np.zeros(S, np.int32)
            self._dsteps = np.ones(S, np.int32)
            self._remask = np.zeros(S, np.int32)
            self._thresh = np.ones(S, np.float32)
            # since start: slot-forwards of live slots, blocks committed,
            # positions unmasked, tokens emitted
            self._forwards = self._blocks = self._unmasked = 0
            self._block_tokens = 0

        # ONE keyed ExecutableRegistry replaces the four parallel executable
        # dicts this engine used to carry (prefill rungs, draft-prefill
        # rungs, verify (family, k) pairs, decode families). Keys are
        # ("serve.<kind>", ...distinguishers); every entry is admitted
        # PINNED — the serving working set must never be LRU-evicted under
        # a live slot (the ISSUE-18 hazard fix: with a tiny
        # FLAGS_decode_jit_cache_size the registry refuses eviction and
        # counts exec.registry.evict_refusals instead of breaking decode).
        # Decode keys stay off prompt length, max_new_tokens, and sampling
        # values — the family strings ("greedy"/"sample") and the ladder
        # rungs bound the executable count exactly as before.
        self._execs = ExecutableRegistry(
            name="serve",
            capacity=lambda: int(_flags.flag("decode_jit_cache_size")))
        # set by precompile() when the backend probe gates AOT off
        self.aot_skip_reason: Optional[str] = None

    # ------------------------------------------------------------- params
    def refresh_params(self) -> None:
        """Re-snapshot model weights (pre-cast once to the AMP compute
        dtype, the weights-in-compute-dtype inference layout legacy
        generate() establishes per call)."""
        import jax.numpy as jnp

        from ..core.dispatch import _autocast_dtype_for

        state = self.model.state_dict(include_non_persistable_buffer=True)
        params = {k: v._data for k, v in state.items()}
        mm_dtype = _autocast_dtype_for("attention", ())
        backbone, _ = self.model.serving_backbone()
        self._cache_dtype = (mm_dtype if mm_dtype is not None
                             else backbone.parameters()[0]._data.dtype)
        w_dtype = _autocast_dtype_for("matmul", ())

        def _cast(params):
            # an array that already has the compute dtype is kept as it is:
            # the snapshot then adds no second copy of it (D16)
            if w_dtype is None:
                return params
            return {k: (v.astype(w_dtype)
                        if v.ndim >= 2 and v.dtype != w_dtype
                        and jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in params.items()}

        self._params = _cast(params)
        if getattr(self, "draft_model", None) is not None:
            dstate = self.draft_model.state_dict(
                include_non_persistable_buffer=True)
            self._dparams = _cast({k: v._data for k, v in dstate.items()})
        else:
            self._dparams = None

    # ------------------------------------------------------------- public
    def submit(self, prompt_ids, max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos_token_id=None, seed: int = 0, trace_ctx=None,
               tenant=None, speculate_k: int = 0,
               denoising_steps: Optional[int] = None,
               remasking: Optional[str] = None,
               confidence_threshold: Optional[float] = None,
               record_blocks: bool = False) -> Request:
        """Enqueue a request; returns the live Request handle (tokens fill
        in as the engine runs). max_new_tokens is clamped to the engine cap
        and to the cache room left after the prompt's bucket. trace_ctx
        (fleet.TraceContext) threads a fleet request id + parent span
        through every span this request records. speculate_k > 0 opts this
        request into speculative decoding (snapped up to the engine's
        spec_ladder rung; needs a draft model). `denoising_steps`,
        `remasking` and `confidence_threshold` are the schedule of a model
        that generates by diffusion over blocks (serving/diffusion.py; None:
        the model's own), and `record_blocks` keeps the request's blocks
        forward by forward in `Request.block_states`; an engine whose model
        generates a token a step refuses them."""
        if self._draining:
            raise RuntimeError(
                "ServingEngine is draining (SIGTERM/begin_drain): admission "
                "is closed; submit to a live replica")
        if speculate_k:
            if speculate_k < 0:
                raise ValueError(
                    f"speculate_k must be >= 0, got {speculate_k}")
            windows = _kvs.window_layers(self._kv.spec)
            if windows:
                raise ValueError(
                    f"speculate_k > 0 cannot be served: layers {windows} "
                    "keep a window of rows as a ring, which the verify "
                    "program's rewind by offset would overwrite")
            if self.draft_model is None:
                raise ValueError(
                    "speculate_k > 0 needs a draft model: construct the "
                    "engine with draft_model=")
        gen = self._diffusion
        asked = {"denoising_steps": denoising_steps, "remasking": remasking,
                 "confidence_threshold": confidence_threshold,
                 "record_blocks": record_blocks or None}
        if gen is None:
            given = [k for k, v in asked.items() if v is not None]
            if given:
                raise ValueError(
                    f"{', '.join(given)} cannot be served: this engine's "
                    "model generates one token a slot a step, and these are "
                    "the schedule of generation by diffusion over blocks")
        else:
            if speculate_k:
                raise ValueError(
                    "speculate_k > 0 cannot be served: this engine's model "
                    "generates by diffusion over blocks")
            denoising_steps = int(gen.denoising_steps if denoising_steps
                                  is None else denoising_steps)
            if denoising_steps < 1:
                raise ValueError(f"denoising_steps must be >= 1, got "
                                 f"{denoising_steps}")
            remasking = gen.remasking if remasking is None else remasking
            remasking_id(remasking)               # raises by name
            confidence_threshold = float(
                gen.confidence_threshold if confidence_threshold is None
                else confidence_threshold)
        req = Request(prompt_ids, max_new_tokens, temperature, top_k, top_p,
                      eos_token_id, seed, trace_ctx=trace_ctx, tenant=tenant,
                      speculate_k=speculate_k,
                      denoising_steps=denoising_steps, remasking=remasking,
                      confidence_threshold=confidence_threshold,
                      record_blocks=record_blocks)
        if gen is not None and (req.prompt_ids == gen.mask_token_id).any():
            raise ValueError(
                f"the prompt holds the mask token {gen.mask_token_id}: a "
                "given token that reads as masked would be overwritten by "
                "the first forward over its block")
        plen = len(req.prompt_ids)
        req.bucket = bucket_for(plen, self.ladder)  # raises if oversize
        room = self.max_seq_len - req.bucket
        req.max_new_tokens = max(1, min(req.max_new_tokens,
                                        self.max_new_cap, room))
        req.submit_ts = time.perf_counter()
        with self._lock:
            req.queue_depth_at_submit = len(self._queue)
            self._queue.append(req)
        tr = _obs_tracer.get_tracer()
        if tr.enabled:
            tr.instant("serve.enqueue", **req.trace_args(
                queue_depth=req.queue_depth_at_submit))
        return req

    def step(self) -> int:
        """Admit queued requests into free slots (bucketed prefill), then
        fetch and deliver ONE decode dispatch (`steps_per_dispatch` tokens
        a live slot). Returns the number of slots live after the delivered
        dispatch (0 = fully drained, nothing in flight).

        While every slot is live and none can end inside the dispatch in
        flight, the next dispatch is enqueued before that one is fetched
        (`_decode_step`), so the device always has a decode chunk queued:
        a token then reaches its `Request` one `step()` after the device
        made it, instead of in the same one. Tokens, their order and each
        request's stream are the same either way; `stats()` gives the share
        of dispatches enqueued ahead (`decode_ahead_share`).

        The spans below are engine-boundary spans (observability/tracer.py
        `boundary`): always recorded, a handful a dispatch, each mirrored
        as a TraceAnnotation so a device trace's idle gaps can be put down
        to what the engine was doing. serve.step > serve.admit > per
        request serve.prefill.dispatch + serve.prefill.sync; then
        serve.decode.dispatch, serve.decode.fetch, serve.emit."""
        tr = _obs_tracer.get_tracer()
        with tr.boundary("serve.step"):
            self._prefill_ms = []      # (dispatch, sync) of this step's prefills
            with tr.boundary("serve.admit") as admit:
                self._admit()
            self._admit_ms = admit.ms
            if self._active.any():
                self._advance_step()
        return int(self._active.sum())

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drive until queue and slots drain (or max_steps decode
        dispatches); returns the requests completed during this call."""
        done0 = len(self._completed)
        steps = 0
        while (self._queue and not self._draining) or self._active.any():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        if steps:
            self._emit_registry_rollup()
        return self._completed[done0:]

    # ---------------------------------------------------- elastic replica
    def register_replica(self, store, replica_id: str,
                         lease_s: Optional[float] = None):
        """Join the serving fleet: heartbeat a ``replica/<rid>`` lease under
        the current membership generation (distributed/membership.py) and
        arm nothing else — call install_sigterm_handler() to make SIGTERM
        drain this replica gracefully. Returns the WorkerAgent."""
        from ..distributed.membership import WorkerAgent

        agent = WorkerAgent(store, replica_id, lease_s=lease_s,
                            kind="replica")
        agent.register()
        agent.start_heartbeat()
        self._replica_agent = agent
        return agent

    def begin_drain(self, reason: str = "drain") -> None:
        """Stop admission NOW (submit() refuses, queued requests stay
        queued for a live replica); active slots keep decoding. Idempotent."""
        if self._draining:
            return
        self._draining = True
        if reason == "sigterm":
            from ..distributed import membership as _membership

            _membership.PREEMPTIONS.increase()
            mreg = _obs_metrics.active_registry()
            if mreg is not None:
                mreg.counter("elastic.preemptions").inc()

    def drain(self, timeout_s: Optional[float] = None) -> List[Request]:
        """Run active slots to completion (admission closed), deregister
        the replica lease, and return the requests completed during the
        drain. Bounded by FLAGS_elastic_drain_timeout_s — a hung decode
        retires the replica anyway rather than hanging the SIGTERM path.
        Records ``elastic.drain_ms`` in the metrics registry."""
        self.begin_drain()
        tmo = float(timeout_s if timeout_s is not None
                    else _flags.flag("elastic_drain_timeout_s"))
        t0 = time.perf_counter()
        done0 = len(self._completed)
        while self._active.any():
            if time.perf_counter() - t0 > tmo:
                # timeout cut the drain short: whatever is still decoding
                # terminates with outcome="drained" (counted, recorded,
                # but not a completion) and its slot is reclaimed
                import numpy as np

                for slot in np.nonzero(self._active)[0]:
                    req = self._slot_req[slot]
                    self._active[slot] = False
                    self._slot_req[slot] = None
                    self._kv.release(slot)
                    if req is not None and req.done_ts is None:
                        self._finish(req, outcome="drained")
                # a chunk still in flight belonged to those requests
                self._inflight = self._carry = None
                break
            self._advance_step()
        drain_ms = (time.perf_counter() - t0) * 1000.0
        mreg = _obs_metrics.active_registry()
        if mreg is not None:
            mreg.histogram("elastic.drain_ms").observe(drain_ms)
        self._emit_registry_rollup()
        self.retire()
        return self._completed[done0:]

    def retire(self) -> None:
        """Deregister the replica lease (graceful leave). Idempotent; a
        no-op when register_replica was never called."""
        if self._replica_agent is not None:
            self._replica_agent.announce_leave(
                "sigterm" if self._draining else "leave")
            self._replica_agent = None

    def install_sigterm_handler(self) -> None:
        """SIGTERM → close admission (drain flag) and chain the previous
        handler. The actual drain runs on the driver thread: run() exits
        its loop once active slots empty (queue is no longer admitted), or
        the owner calls drain() explicitly. Signal-handler work is kept to
        a flag flip — no jax dispatch from an async context."""
        def _on_sigterm(signum, frame):
            self.begin_drain("sigterm")
            prev = self._prev_sigterm
            if callable(prev):
                prev(signum, frame)

        self._prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)

    def stats(self) -> Dict[str, Any]:
        """Counts of this engine: `steps` (decode steps delivered),
        `decode_dispatches` and `decode_ahead_share` (plain decode dispatches,
        and the share of them enqueued before the dispatch ahead of them was
        fetched: `_decode_step`), `completed`, `queued`, `active_slots`,
        `draining`, the ladder and executable counts, the cache's layout and
        bytes; with a draft model the verify counts, on the paged layout the
        pool's and the prefix cache's; for a model that generates by diffusion
        over blocks `forwards` (slot-forwards of live slots),
        `blocks_committed`, `positions_unmasked`, `forwards_per_block` and
        `tokens_per_forward` since start; `startup`, the span ring's table
        (`observability/tracer.py` `phase_table`: where the process's time
        went by span, which executable compiled cold, which jit no registry
        holds; one pass over the ring, tens of milliseconds when it is
        full)."""
        out = {
            "steps": self._steps,
            "decode_dispatches": self._decode_dispatches,
            "decode_ahead_share": (self._decode_ahead
                                   / max(1, self._decode_dispatches)),
            "completed": len(self._completed),
            "queued": len(self._queue),
            "active_slots": int(self._active.sum()),
            "draining": self._draining,
            "slot_count": self.slot_count,
            "ladder": self.ladder,
            "prefill_executables": self._execs.count("serve.prefill"),
            "decode_executables": self._execs.count("serve.decode"),
            "kv_layout": self.kv_layout,
            "kv_cache_bytes": self.kv_cache_bytes(),
            "startup": _obs_tracer.phase_table(),
        }
        if self._diffusion is not None:
            out.update({
                "forwards": self._forwards,
                "blocks_committed": self._blocks,
                "positions_unmasked": self._unmasked,
                "forwards_per_block": self._forwards / max(1, self._blocks),
                "tokens_per_forward": (self._block_tokens
                                       / max(1, self._forwards)),
            })
        if self.draft_model is not None:
            out.update({
                "spec_ladder": self.spec_ladder,
                "verify_executables": self._execs.count("serve.verify"),
                "draft_prefill_executables":
                    self._execs.count("serve.dprefill"),
            })
        if self.kv_layout == "paged":
            out.update({
                "page_tokens": self._kv.page_tokens,
                "num_pages": self._kv.num_pages,
                "pages_in_use": self._kv.pool.in_use,
                "pages_cached": self._kv.pool.cached,
                "prefix": self._kv.prefix.stats(),
            })
        return out

    # ------------------------------------------------------ paged public
    def kv_cache_bytes(self) -> int:
        """Device bytes held by the KV cache: per-slot rows (contiguous)
        or pools + scales + page tables (paged) — the denominator of
        serve_bench's concurrent-requests-per-MB datum."""
        return self._kv.nbytes()

    def prefix_match_len(self, prompt_ids) -> int:
        """Tokens of this prompt already cached as shared pages (0 on the
        contiguous layout) — the router's prefix-locality probe; no
        refcount side effects."""
        if self.kv_layout != "paged":
            return 0
        return self._kv.prefix.peek([int(t) for t in prompt_ids])

    def flush_prefix_cache(self) -> int:
        """Evict every refcount-zero cached prefix page; returns the count
        freed. Bench hygiene: measure cold-trie TTFT against warm
        executables."""
        if self.kv_layout != "paged":
            return 0
        return self._kv.prefix.flush()

    def occupancy(self) -> float:
        return float(self._active.sum()) / self.slot_count

    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def slot_cache(self):
        """The slot cache (kv_state.SlotCache or kv_pages.PagedSlotCache):
        for checks and rehearsals to read what a slot holds (`k[l]`, `v[l]`
        as [slots, rows, kv_heads, head_dim], `state[l]`, `tail[l]`), never
        to write."""
        return self._kv

    # ---------------------------------------------------------- internals
    @property
    def _kcs(self):
        """The contiguous cache's key arrays, one [slots, rows, kv_heads,
        head_dim] a layer (None on the paged layout): read-only, for the
        benchmark's row check and rehearsals until they get public names.
        They are `SlotCache.k`, the logical view: the programs' arguments
        are `k_stored` (kv_state.py), padded to the shape whose default
        device layout the decode loop keeps, so a rehearsal that builds its
        argument shapes from these compiles the program without the pad
        (tools/decode_hlo_probe.py --serving compiles the one served)."""
        return getattr(self._kv, "k", None)

    @property
    def _vcs(self):
        return getattr(self._kv, "v", None)

    @staticmethod
    def _donate(first: int, *caches) -> tuple:
        """donate_argnums of a program whose cache arguments start at
        `first`: every argument of the caches, in order."""
        return tuple(range(first, first + sum(c.n_args for c in caches)))

    @property
    def _exec_stash(self):
        """label -> (jitted fn, abstract args), now owned by the registry
        (introspect_executables / analysis / mem_report read this view)."""
        return self._execs.stash_map()

    @property
    def _exec_donated(self):
        """label -> donate_argnums of the stashed fn (default_contracts
        derives each label's donation floor from these positions)."""
        return self._execs.donated_map()

    def exec_registry(self) -> ExecutableRegistry:
        """This engine's ExecutableRegistry (every prefill/decode/verify/
        draft executable, plus the AOT fast paths precompile() installs)."""
        return self._execs

    def _stash_exec(self, label: str, fn, call_args,
                    donate: tuple = (1, 2)) -> None:
        """First call per label: remember (jitted fn, abstract args) so
        introspect_executables() can AOT-lower the same program later, and
        auto-capture now when FLAGS_exec_introspect is on. ShapeDtypeStructs
        replace the arrays — no live (or donated) buffer is retained.
        donate records the fn's donate_argnums for default_contracts()."""
        self._execs.stash(label, fn, call_args, donate=donate)

    def introspect_executables(self, force: bool = False) -> Dict[str, dict]:
        """Capture XLA memory_analysis()/cost_analysis() for every prefill/
        decode executable this engine has dispatched (label -> stats dict;
        mirrored into registry gauges exec.<label>.* when metrics are
        active). Costs one extra AOT compile per uncaptured label."""
        out = {}
        for label, (fn, avals) in list(self._exec_stash.items()):
            out[label] = _obs_exec.capture_jit(label, fn, avals, force=force)
        return out

    # ---- static analysis (paddle_tpu.analysis) --------------------------
    def default_contracts(self) -> list:
        """Hygiene on every serve label (a host transfer inside prefill or
        decode would serialize the whole fleet on one Python callback) plus
        per-label KV-cache donation coverage: args 1/2 of every stashed
        signature are the caches this engine donates, so their byte size IS
        the aliasing floor."""
        import numpy as np

        from .. import analysis as _an

        cs = [_an.ProgramContract(label="serve.*", name="serve-hygiene")]
        for label, (fn, avals) in sorted(self._exec_stash.items()):
            try:
                import jax

                # contiguous: args 1/2 are the K/V caches; paged: arg 1 is
                # the whole pool state (pools + scales + page tables) — the
                # recorded donate_argnums say which, and their byte size IS
                # the aliasing floor either way
                dargs = self._exec_donated.get(label, (1, 2))
                caches = jax.tree_util.tree_leaves(
                    tuple(avals[i] for i in dargs))
                donated = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                              for a in caches)
            except Exception:
                continue
            if donated:
                cs.append(_an.ProgramContract(
                    label=label, donated_bytes=donated,
                    name=f"{label}-cache-donation"))
        return cs

    def analyze(self, contracts=None, dump=None):
        """Run the static-analysis pass suite over every prefill/decode
        executable this engine has dispatched (see paddle_tpu.analysis).
        Dispatch-free — programs AOT-lower from the stashed signatures."""
        from .. import analysis as _an

        progs = _an.programs_from_stash(self._exec_stash)
        if contracts is None:
            contracts = self.default_contracts()
        return _an.PassManager().run(progs, contracts, dump=dump)

    def _program_device_span(self) -> int:
        """Devices a single serving executable spans. The engine keeps
        params/KV on the default device and compiles no collectives, so
        the span is 1 regardless of how many devices the process exposes;
        a future sharded serving mesh widens this (and the AOT gate with
        it)."""
        return 1

    # ---- AOT ladder precompilation (ISSUE 18) ---------------------------
    def precompile(self, families: Sequence[str] = ("greedy", "sample"),
                   force: bool = False) -> Dict[str, Any]:
        """AOT-compile the full serving ladder before the first request:
        every (prefill rung x sampling family x spec rung) executable is
        lowered at its exact dispatch signature and compiled via
        ``jit(...).lower().compile()``, then installed as the registry
        entry's dispatch fast path. With FLAGS_compile_cache_dir pointing
        at an AOT bundle (tools/aot_bundle.py) every compile deserializes
        WARM — a fresh replica joins the fleet with zero cold compiles.

        Gated by analysis.backend.aot_serving_reason(): cache-served
        multi-device executables are nondeterministic on this jax's CPU, so
        a multi-device CPU serving mesh skips (reason recorded in
        ``aot_skip_reason`` and the returned dict) unless ``force``. The
        probe keys on the device span of the PROGRAMS this engine compiles
        — one device until serving grows a mesh — not the process device
        count: an 8-virtual-device drill process still precompiles its
        single-device replicas.

        Returns {"precompiled", "skipped", "cold", "warm", "wall_ms"}."""
        import jax.numpy as jnp
        import numpy as np

        from ..analysis.backend import aot_serving_reason
        from ..core import monitor

        reason = None if force else aot_serving_reason(
            device_count=self._program_device_span())
        if reason is not None:
            self.aot_skip_reason = reason
            monitor.stat("serving.aot_skipped").increase()
            return {"precompiled": 0, "skipped": reason,
                    "cold": 0, "warm": 0, "wall_ms": 0.0}
        self.aot_skip_reason = None
        kv, dkv = self._kv, self._dkv
        S = self.slot_count

        def slot_vecs():
            return (jnp.asarray(self._offsets), jnp.asarray(self._last_tok),
                    jnp.asarray(self._active))

        def sampling_vecs():
            return (jnp.asarray(self._temps), jnp.asarray(self._topk),
                    jnp.asarray(self._topp), jnp.asarray(self._eos),
                    jnp.asarray(self._remaining), jnp.asarray(self._seeds))

        plan = []  # (key, build, label, donate, call_args)
        blocks = self._diffusion is not None
        for bucket in self.ladder:
            padded = jnp.asarray(np.zeros((1, bucket), np.int64))
            at = tuple(jnp.int32(0) for _ in kv.prefill_at)
            args = (self._params, *kv.args(), padded, jnp.int32(0), *at)
            if not blocks:
                args += (jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0),
                         jnp.int32(0))
            plan.append((("serve.prefill", bucket),
                         (lambda b=bucket: (self._build_block_prefill(b)
                                            if blocks else
                                            self._build_prefill(b))),
                         f"serve.prefill_b{bucket}", self._donate(1, kv),
                         args))
            if dkv is not None:
                dargs = (self._dparams, *dkv.args(), padded, jnp.int32(0),
                         jnp.int32(0))
                plan.append((("serve.dprefill", bucket),
                             (lambda b=bucket:
                              self._build_draft_prefill(b)),
                             f"serve.dprefill_b{bucket}",
                             self._donate(1, dkv), dargs))
        for family in families:
            if blocks:
                args = (self._params, *kv.args(), *(
                    jnp.asarray(a) for a in (*self._host_carry(),
                                             *self._host_consts())))
            else:
                args = (self._params, *kv.args(), *slot_vecs(),
                        *sampling_vecs())
            plan.append((("serve.decode", family),
                         (lambda f=family: (self._build_block_decode(f)
                                            if blocks else
                                            self._build_decode(f))),
                         f"serve.decode_{family}", self._donate(1, kv), args))
            if dkv is None:
                continue
            for k in self.spec_ladder:
                n_draft = jnp.asarray(np.zeros(S, np.int32))
                args = (self._params, self._dparams, *kv.args(), *dkv.args(),
                        *slot_vecs(), n_draft, *sampling_vecs())
                plan.append((("serve.verify", family, k),
                             (lambda f=family, kk=k:
                              self._build_verify(f, kk)),
                             f"serve.verify_{family}_k{k}",
                             self._donate(2, kv, dkv), args))

        from ..core import compile_cache as _compile_cache

        cold0 = monitor.stat("engine.compile_cold").get()
        warm0 = monitor.stat("engine.compile_warm").get()
        t0 = time.perf_counter()
        n = 0
        for key, build, label, donate, call_args in plan:
            entry = self._execs.get_or_build(key, build, label=label,
                                             donate=donate, pin=True)
            if entry.aot is None or force:
                self._execs.precompile(entry, call_args)
                n += 1
            self._stash_exec(label, entry.fn, call_args, donate=donate)
        wall_ms = (time.perf_counter() - t0) * 1000.0
        monitor.stat("serving.aot_precompiles").increase(n)
        return {"precompiled": n, "skipped": None,
                "cold": monitor.stat("engine.compile_cold").get() - cold0,
                "warm": monitor.stat("engine.compile_warm").get() - warm0,
                "wall_ms": wall_ms,
                "cache_dir": _compile_cache.cache_dir()}

    def _emit_registry_rollup(self) -> None:
        """Cumulative exec-registry rollup record for the trace sink /
        flight recorder (trace_summary's per-label registry table)."""
        fr = _obs_flight.get()
        if self.sink is None and fr is None:
            return
        rec = dict(self._execs.rollup(), event="exec_registry",
                   ts=time.time())
        if self.sink is not None:
            self.sink.write(rec)
        if fr is not None:
            fr.record(rec)

    @staticmethod
    def _head_traced(model, params, h_arr):
        """`model`'s hidden states -> logits with weights from traced
        params."""
        from ..core.autograd import no_grad
        from ..core.tensor import Tensor
        from ..jit import _swapped_state, _tracing

        with _swapped_state(model, params), _tracing(), no_grad():
            return model._head_logits(Tensor(h_arr))._data

    @staticmethod
    def _backbone(model, params, ids, caches):
        """`model`'s backbone over `ids` with weights from traced params ->
        (hidden states, new caches, what the step reports of itself: {} for
        a model that reports nothing)."""
        from ..core.tensor import Tensor
        from ..jit import functional_call

        layer, prefix = model.serving_backbone()
        own = {k[len(prefix):]: v for k, v in params.items()
               if k.startswith(prefix)}
        out = functional_call(layer, own, Tensor(ids), caches=caches)
        return out[0]._data, out[1], (out[2] if len(out) > 2 else {})

    # ---- prefill -------------------------------------------------------
    def _build_prefill(self, bucket: int):
        """One executable per prompt rung: `length` tokens (the prompt, or on
        the paged layout its unshared tail from a traced `base`) right-padded
        to the rung run through the model, and the cache commits their rows
        to the slot. Where the request goes (`at`: the cache's `prefill_at`),
        its length, sampling params and seed are all traced, so a whole
        traffic distribution, prefix hits of any depth included, shares
        O(#rungs) executables."""
        import jax

        from .sampling import request_key, sample_tokens

        kv = self._kv

        def prefill(params, *args):
            cache, (ids, length, *at, temp, top_k, top_p, seed) = _split(
                args, kv.n_args)
            caches = kv.prefill_views(cache, bucket, length, *at)
            h, caches, _ = self._backbone(self.model, params, ids, caches)
            # queries past `length` are the pad's: discarded
            last_h = jax.lax.dynamic_index_in_dim(h, length - 1, 1,
                                                  keepdims=False)
            logits = self._head_traced(self.model, params, last_h)  # [1, V]
            key = request_key(seed, kv.first_position(length, *at))
            tok = sample_tokens(logits, key[None], temp[None], top_k[None],
                                top_p[None])[0]
            return (*kv.commit_prefill(cache, caches, length, *at), tok)

        return jax.jit(jax.named_scope("prefill")(prefill),
                       donate_argnums=self._donate(1, kv))

    def _build_block_prefill(self, bucket: int):
        """A prompt's whole blocks for a model that generates by diffusion:
        `length` tokens (a multiple of the block length) right-padded to the
        rung run through the backbone under the block-causal mask, and the
        cache commits their rows to the slot. The pad starts on a block's
        edge, so no real query sees it. No logits and no token: a request's
        first tokens come from its first committed block."""
        import jax

        kv = self._kv

        def block_prefill(params, *args):
            cache, (ids, length, *at) = _split(args, kv.n_args)
            caches = kv.prefill_views(cache, bucket, length, *at)
            _h, caches, _ = self._backbone(self.model, params, ids, caches)
            return kv.commit_prefill(cache, caches, length, *at)

        return jax.jit(jax.named_scope("prefill")(block_prefill),
                       donate_argnums=self._donate(1, kv))

    def _admit_block(self, req: Request, slot: int) -> None:
        """Seat a request of a model that generates by diffusion: its whole
        blocks are prefilled (none for a prompt shorter than a block), the
        prompt tokens left over open the first block as given tokens."""
        gen = self._diffusion
        B = gen.block_length
        head = len(req.prompt_ids) // B * B
        req.admit_ts = time.perf_counter()    # queue wait ends here
        self._note_queue_wait(req)
        if head:
            self._run_prefill(
                req, req.bucket, req.prompt_ids[:head], (slot,),
                req.trace_args(bucket=req.bucket, slot=slot))
        given = req.prompt_ids[head:]
        req.given_in_block, req.block_offset = len(given), head
        self._seat(req, slot, head, 0, req.max_new_tokens)
        self._block[slot] = gen.mask_token_id
        self._block[slot, :len(given)] = given
        self._block_k[slot] = 0
        self._given[slot] = len(given)
        self._dsteps[slot] = req.denoising_steps
        self._remask[slot] = remasking_id(req.remasking)
        self._thresh[slot] = req.confidence_threshold

    # ---- speculative decoding: draft prefill ---------------------------
    def _build_draft_prefill(self, bucket: int):
        """Draft-model prompt prefill, one executable per prompt rung.
        Writes the draft K/V for positions 0..plen-1 into the slot's row
        of the (always contiguous) draft cache — no sampling, no logits:
        the draft's first proposal comes out of the verify program's scan.
        Right-pad junk past plen is inert: every padded position is
        rewritten by a later draft scan step before any query attends it,
        the same argument the target prefill pad relies on."""
        import jax
        import jax.numpy as jnp

        dkv = self._dkv

        def prefill(dparams, *args):
            cache, (ids, length, slot) = _split(args, dkv.n_args)
            caches = dkv.prefill_views(cache, bucket, length, slot)
            _h, caches, _ = self._backbone(self.draft_model, dparams, ids,
                                           caches)
            return dkv.commit_prefill(cache, caches, length, slot)

        return jax.jit(jax.named_scope("prefill")(prefill),
                       donate_argnums=self._donate(1, dkv))

    def _seat_spec(self, req: Request, slot: int) -> None:
        """Per-seat speculative setup, called at every seating site (slot
        reuse must clear a predecessor's rung). Spec requests snap their
        speculate_k UP to the nearest ladder rung and get a draft-model
        prompt prefill; for paged full-hit replay seats the draft still
        prefills the whole prompt (the draft cache is contiguous and has
        no prefix sharing — position plen-1's verify-scan rewrite is a
        same-value overwrite)."""
        import jax.numpy as jnp
        import numpy as np

        from ..core import monitor

        if req.speculate_k <= 0 or self.draft_model is None:
            self._spec_k[slot] = 0
            return
        rung = self.spec_ladder[-1]
        for r in self.spec_ladder:
            if r >= req.speculate_k:
                rung = r
                break
        self._spec_k[slot] = rung
        bucket = req.bucket
        plen = len(req.prompt_ids)
        dkv = self._dkv
        label, donate = f"serve.dprefill_b{bucket}", self._donate(1, dkv)
        entry = self._execs.get_or_build(
            ("serve.dprefill", bucket),
            lambda: self._build_draft_prefill(bucket),
            label=label, donate=donate, pin=True)
        padded = np.zeros((1, bucket), np.int64)
        padded[0, :plen] = req.prompt_ids
        call_args = (self._dparams, *dkv.args(), jnp.asarray(padded),
                     jnp.int32(plen), jnp.int32(slot))
        self._stash_exec(label, entry.fn, call_args, donate=donate)
        monitor.stat("serving.draft_prefill_dispatches").increase()
        p0 = self._execs.persistent_before(entry)
        t0 = time.perf_counter()
        dkv.take(entry(*call_args))
        self._execs.note_compiles(
            entry, wall_s=time.perf_counter() - t0, persistent_before=p0,
            counter="serving.draft_prefill_compiles")

    def _run_prefill(self, req: Request, bucket: int, prompt, at,
                     span_args: dict) -> Optional[int]:
        """Dispatch the rung's prefill program over `prompt` (the whole
        prompt, or its unshared tail) at `at` and sync on the first token.
        For a model that generates by diffusion over blocks `prompt` is the
        prompt's whole blocks, the program draws nothing and nothing is read
        back (the decode dispatch behind it is what the host waits for): ->
        None. A failure dumps to the flight recorder, finishes the request
        as an error and re-raises."""
        import jax.numpy as jnp
        import numpy as np

        from ..core import monitor

        kv = self._kv
        tr = _obs_tracer.get_tracer()
        blocks = self._diffusion is not None
        try:
            with tr.boundary("serve.prefill.dispatch",
                             **span_args) as dispatch:
                label, donate = f"serve.prefill_b{bucket}", self._donate(1, kv)
                build = (self._build_block_prefill if blocks
                         else self._build_prefill)
                entry = self._execs.get_or_build(
                    ("serve.prefill", bucket), lambda: build(bucket),
                    label=label, donate=donate, pin=True)
                padded = np.zeros((1, bucket), np.int64)
                padded[0, :len(prompt)] = prompt
                call_args = (self._params, *kv.args(), jnp.asarray(padded),
                             jnp.int32(len(prompt)),
                             *(jnp.int32(a) for a in at))
                if not blocks:
                    call_args += (jnp.float32(req.temperature),
                                  jnp.int32(req.top_k),
                                  jnp.float32(req.top_p), jnp.int32(req.seed))
                self._stash_exec(label, entry.fn, call_args, donate=donate)
                monitor.stat("serving.prefill_dispatches").increase()
                p0 = self._execs.persistent_before(entry)
                t0 = time.perf_counter()
                cache, tok = _split(entry(*call_args), kv.n_args)
                kv.take(cache)
                self._execs.note_compiles(
                    entry, wall_s=time.perf_counter() - t0,
                    persistent_before=p0, counter="serving.prefill_compiles")
            if blocks:
                first, sync_ms = None, 0.0
            else:
                with tr.boundary("serve.prefill.sync", **span_args) as sync:
                    first = int(tok[0])           # device sync = first token
                sync_ms = sync.ms
            self._prefill_ms.append((dispatch.ms, sync_ms))
        except Exception as e:
            fr = _obs_flight.get()
            if fr is not None:
                fr.dump("serve_prefill_exception",
                        {"request": req.id, "bucket": bucket,
                         "at": [int(a) for a in at], "error": repr(e)})
            self._finish(req, outcome="error")
            raise
        done = time.perf_counter()
        if tr.enabled:
            tr.record_complete("serve.prefill", req.admit_ts, done, span_args)
        if blocks:          # its first tokens come with its first block
            return None
        req.first_token_ts = done
        mreg = _obs_metrics.active_registry()
        if mreg is not None:
            mreg.histogram("serve.prefill_ms").observe(
                (req.first_token_ts - req.admit_ts) * 1e3)
        return first

    def _note_queue_wait(self, req: Request) -> None:
        tr = _obs_tracer.get_tracer()
        if tr.enabled:
            tr.record_complete("serve.queue_wait", req.submit_ts,
                               req.admit_ts, req.trace_args())
        mreg = _obs_metrics.active_registry()
        if mreg is not None:
            mreg.histogram("serve.queue_wait_ms").observe(
                req.queue_wait_s * 1e3)

    def _seat(self, req: Request, slot: int, offset: int, last_tok: int,
              remaining: int) -> None:
        """Hand `slot` to `req` for decode: `offset` positions held,
        `last_tok` the next step's input, `remaining` tokens still owed."""
        eos = req.eos_token_id
        req.slot = slot
        self._offsets[slot] = offset
        self._last_tok[slot] = last_tok
        self._active[slot] = True
        self._temps[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        self._eos[slot] = eos if eos is not None else _NO_EOS
        self._remaining[slot] = remaining
        self._seeds[slot] = req.seed
        self._slot_req[slot] = req
        self._carry = self._consts = None     # the device's copies are stale
        self._seat_spec(req, slot)

    def _seat_after_prefill(self, req: Request, slot: int,
                            first: int) -> None:
        """The prefill's first token is the request's; seat it for decode
        unless that token already ends it."""
        req.slot = slot
        req.tokens.append(first)
        self._count_tokens(1)
        hit_eos = req.eos_token_id is not None and first == req.eos_token_id
        if hit_eos or req.max_new_tokens <= 1:
            req.finish_reason = "eos" if hit_eos else "length"
            self._kv.release(slot)
            self._finish(req)
            return
        self._seat(req, slot, len(req.prompt_ids), first,
                   req.max_new_tokens - 1)

    def _admit(self) -> None:
        # a chunk in flight was enqueued with every slot live: a slot it
        # shows free (an EOS nobody could foresee) is seated once the chunk
        # is fetched, or the fetch would overwrite the seat
        if self._draining or self._inflight is not None:
            return
        while True:
            with self._lock:
                if not self._queue:
                    return
                free = [i for i in range(self.slot_count)
                        if not self._active[i] and self._slot_req[i] is None]
                if not free:
                    return
                req = self._queue.popleft()
            slot = free[0]
            if self._diffusion is not None:
                self._admit_block(req, slot)
                continue
            if self.kv_layout == "paged":
                if not self._admit_paged(req, slot):
                    return
                continue
            req.admit_ts = time.perf_counter()    # queue wait ends here
            self._note_queue_wait(req)
            first = self._run_prefill(
                req, req.bucket, req.prompt_ids, (slot,),
                req.trace_args(bucket=req.bucket, slot=slot))
            self._seat_after_prefill(req, slot, first)

    # ---- paged admission ----------------------------------------------
    def _pages_reserved_inflight(self) -> int:
        """Worst-case pages still to be allocated by active slots (each
        slot's final offset is offsets + remaining; shared and own pages
        already in its table row don't count)."""
        import numpy as np

        kv = self._kv
        total = 0
        for i in np.nonzero(self._active)[0]:
            end = min(int(self._offsets[i]) + int(self._remaining[i]),
                      self.max_seq_len)
            need = -(-end // kv.page_tokens) - int((kv.tables[i] != 0).sum())
            total += max(0, need)
        return total

    def _admit_paged(self, req: Request, slot: int) -> bool:
        """Seat a request on the paged cache. Three admission shapes:

        - trie miss: allocate prompt pages, prefill the whole prompt
          (base 0) — the contiguous flow, just scattered through pages.
        - partial hit: copy the matched pages into the table row and
          prefill only the unshared tail rung at base = matched tokens.
        - full hit (prompt length is page-aligned and fully cached): NO
          prefill dispatch at all — the slot seats directly into decode at
          offset plen-1 feeding prompt[-1], with a per-row replay flag
          that redirects that first step's (already-cached) K/V write to
          the scratch page. The first token then falls out of the decode
          chunk, sampled with the same request_key(seed, plen) the prefill
          program would have used.

        Returns False (request requeued) when the pool can't cover this
        request's worst case plus in-flight reservations — admission
        retries once decode retires a slot and frees pages."""
        from ..core import monitor
        from .kv_pages import PoolExhausted

        kv = self._kv
        pt = kv.page_tokens
        plen = len(req.prompt_ids)
        req.admit_ts = time.perf_counter()    # queue wait ends here
        shared = kv.prefix.match(req.prompt_ids)
        k_shared = len(shared)
        monitor.stat("serving.prefix_lookups").increase()
        # reservation check: this request's unshared worst case on top of
        # what active slots may still allocate must fit free + evictable
        need_new = -(-(plen + req.max_new_tokens) // pt) - k_shared
        avail = kv.pool.available
        if avail < self._pages_reserved_inflight() + need_new:
            for p in shared:
                kv.prefix.release(int(p))
            if not self._active.any():
                raise PoolExhausted(
                    f"pool of {kv.num_pages} pages cannot fit one request "
                    f"needing {need_new} fresh pages ({avail} available) — "
                    "raise kv_num_pages or lower max_new_cap")
            req.admit_ts = None
            with self._lock:
                self._queue.appendleft(req)
            return False
        if shared:
            monitor.stat("serving.prefix_hits").increase()
            req.prefix_hit = True
            req.shared_tokens = k_shared * pt
        kv.tables[slot, :] = 0
        kv.tables[slot, :k_shared] = shared
        kv.slot_pages[slot] = [int(p) for p in shared]
        self._note_queue_wait(req)

        if k_shared * pt >= plen:
            # full hit: replay seat, zero prefill dispatches
            monitor.stat("serving.prefill_skips").increase()
            req.tail_bucket = 0
            tr = _obs_tracer.get_tracer()
            if tr.enabled:
                tr.instant("serve.prefix_replay", **req.trace_args(
                    slot=slot, shared_tokens=req.shared_tokens))
            self._seat(req, slot, plen - 1, int(req.prompt_ids[-1]),
                       req.max_new_tokens)
            kv.replay[slot] = True
            return True

        # partial hit / miss: allocate the prompt's unshared pages and
        # prefill the tail rung at base = shared tokens
        base = k_shared * pt
        tbucket = bucket_for(plen - base, self.ladder)
        req.tail_bucket = tbucket
        npages_prompt = -(-plen // pt)
        if not kv.prefix.ensure_free(npages_prompt - k_shared):
            raise PoolExhausted(          # reservation check above makes
                "page reservation accounting violated")  # this unreachable
        for pi in range(k_shared, npages_prompt):
            page = kv.pool.alloc()
            kv.tables[slot, pi] = page
            kv.slot_pages[slot].append(page)
        first = self._run_prefill(
            req, tbucket, req.prompt_ids[base:], (base, slot),
            req.trace_args(bucket=tbucket, base=base, slot=slot))
        # publish this prompt's fully-written pages for future sharers
        full_pages = plen // pt
        if full_pages > k_shared:
            kv.prefix.insert(
                req.prompt_ids[:full_pages * pt],
                [int(p) for p in kv.tables[slot, :full_pages]])
        self._seat_after_prefill(req, slot, first)
        return True

    # ---- decode --------------------------------------------------------
    def _build_decode(self, family: str):
        """The continuous-batching decode chunk, ONE executable a sampling
        family on either layout: `steps_per_dispatch` single-token steps in
        a scan over the donated slot cache."""
        import jax
        import jax.numpy as jnp

        from .sampling import request_key, sample_tokens

        T = self.max_seq_len
        n_inner = self.steps_per_dispatch
        greedy_only = family == "greedy"
        kv = self._kv

        def step_chunk(params, *args):
            cache, (off, tok, active, temps, top_k, top_p, eos, remaining,
                    seeds) = _split(args, kv.n_args)

            def one(carry, _):
                cache, off, tok, active, remaining = carry
                caches = kv.views(cache, kv.tip(off), active)
                h, caches, stats = self._backbone(
                    self.model, params, tok[:, None].astype(jnp.int64),
                    caches)
                logits = self._head_traced(self.model, params,
                                           h[:, 0])  # [S, V]
                act = active.astype(jnp.int32)
                new_off = off + act         # the sampled token's position
                if greedy_only:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    keys = jax.vmap(request_key)(seeds, new_off)
                    # a retired slot draws as a greedy row: no search
                    nxt = sample_tokens(logits, keys,
                                        jnp.where(active, temps, 0.0),
                                        top_k, top_p)
                nxt = jnp.where(active, nxt, tok)
                new_remaining = remaining - act
                hit_eos = active & (eos != _NO_EOS) & (nxt == eos)
                new_active = (active & ~hit_eos & (new_remaining > 0)
                              & (new_off < T))
                return ((kv.absorb(cache, caches, active), new_off, nxt,
                         new_active, new_remaining),
                        (nxt, active, hit_eos, stats))

            carry = (cache, off, tok, active, remaining)
            (cache, off, tok, active, remaining), (
                toks, was_active, hits, stats) = jax.lax.scan(
                one, carry, None, length=n_inner)
            # toks/was_active/hits: [n_inner, S]; stats: what the model
            # reports of a step ({} for most), folded over the fused steps
            return (*cache, off, tok, active, remaining, toks, was_active,
                    hits, self._fold_step_stats(stats))

        return jax.jit(jax.named_scope("decode")(step_chunk),
                       donate_argnums=self._donate(1, kv))

    def _build_block_decode(self, family: str):
        """The decode chunk of a model that generates by diffusion over
        blocks (serving/diffusion.py), ONE executable a sampling family:
        `steps_per_dispatch` forwards in a scan over the donated slot cache,
        each over a block of B positions a slot. The carry holds a slot's
        block `x` [slots, B], the forwards it has had (`k`) and the given
        tokens that open it (`given`) beside `off`, `active` and `remaining`
        (tokens still owed). One forward serves slots in any phase: a slot
        whose block has no mask left COMMITS (its offset moves by B, so the
        rows this forward wrote are the block's; the block goes out and the
        next starts all mask), every other draws a token and its confidence
        at every position and unmasks the chosen; both are selects over the
        same forward. A forward returns the blocks as it left them (a
        committed block as it was committed), which slots were live and which
        committed, the positions it unmasked, its draws and confidences;
        whether a request ended at an end token the host reads off the
        block."""
        import jax
        import jax.numpy as jnp

        from . import diffusion
        from .sampling import request_key, sample_tokens_with_prob

        T = self.max_seq_len
        n_inner = self.steps_per_dispatch
        greedy_only = family == "greedy"
        kv = self._kv
        B, mask_id = (self._diffusion.block_length,
                      self._diffusion.mask_token_id)
        _obs_metrics.default_registry().counter(
            "diffusion.calls.block_step",
            "block-diffusion decode programs traced").inc()

        def block_chunk(params, *args):
            cache, (off, x, k, given, active, remaining, temps, top_k, top_p,
                    eos, seeds, dsteps, remask, thresh) = _split(
                args, kv.n_args)
            S = off.shape[0]
            at = jnp.arange(B, dtype=jnp.int32)[None, :]

            def one(carry, _):
                cache, off, x, k, given, active, remaining = carry
                caches = kv.views(cache, kv.tip(off), active)
                h, caches, stats = self._backbone(
                    self.model, params, x.astype(jnp.int64), caches)
                logits = self._head_traced(
                    self.model, params,
                    h.reshape((S * B, -1))).astype(jnp.float32)  # [S*B, V]
                with jax.named_scope("denoise"):
                    # a trained model never predicts the mask token; random
                    # weights would, and the position would stay masked
                    logits = logits.at[:, mask_id].set(-jnp.inf)
                    masked = x == mask_id
                    commit = active & ~masked.any(axis=1)
                    with jax.named_scope("confidence"):
                        if greedy_only:
                            x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                            conf = jnp.exp(
                                jnp.max(logits, axis=-1)
                                - jax.nn.logsumexp(logits, axis=-1))
                        else:
                            # one stream a (request, position, forward)
                            keys = jax.vmap(lambda s, p, i: jax.random.fold_in(
                                request_key(s, p), i))(
                                jnp.repeat(seeds, B),
                                (off[:, None] + at).reshape(-1),
                                jnp.repeat(k, B))
                            # a retired slot draws as a greedy row: no search
                            x0, conf = sample_tokens_with_prob(
                                logits, keys,
                                jnp.repeat(jnp.where(active, temps, 0.0), B),
                                jnp.repeat(top_k, B), jnp.repeat(top_p, B))
                        x0, conf = x0.reshape((S, B)), conf.reshape((S, B))
                    with jax.named_scope("unmask"):
                        take = diffusion.unmask(
                            masked, conf, diffusion.share(B, dsteps, k),
                            remask, thresh)
                        take = take & (active & ~commit)[:, None]
                        left = jnp.where(take, x0, x)
                    with jax.named_scope("commit"):
                        # of a committed block, the positions that are the
                        # request's output: past the given, inside the budget
                        out = (at >= given[:, None]) & (
                            at < (given + remaining)[:, None])
                        hit_eos = commit & (eos != _NO_EOS) & (
                            out & (x == eos[:, None])).any(axis=1)
                        new_off = jnp.where(commit, off + B, off)
                        new_remaining = jnp.where(
                            commit, remaining - (B - given), remaining)
                        new_active = active & ~(commit & (
                            hit_eos | (new_remaining <= 0)
                            | (new_off + B > T)))
                        new_x = jnp.where(commit[:, None], mask_id, left)
                        new_k = jnp.where(commit, 0,
                                          k + active.astype(jnp.int32))
                        new_given = jnp.where(commit, 0, given)
                return ((kv.absorb(cache, caches, active), new_off, new_x,
                         new_k, new_given, new_active, new_remaining),
                        (left, active, commit,
                         take.sum(axis=1, dtype=jnp.int32), x0, conf, stats))

            carry = (cache, off, x, k, given, active, remaining)
            (cache, *carry), (*outs, stats) = jax.lax.scan(
                one, carry, None, length=n_inner)
            # outs: [n_inner, S(, B)] each; stats folded over the forwards
            return (*cache, *carry, *outs, self._fold_step_stats(stats))

        return jax.jit(jax.named_scope("decode")(block_chunk),
                       donate_argnums=self._donate(1, kv))

    # ---- speculative decoding: verify ----------------------------------
    def _spec_commit(self, jax, jnp, logits, dlogits_sk, props, off, tok,
                     active, n_draft, temps, top_k, top_p, eos, remaining,
                     seeds, k, greedy_only):
        """Acceptance + commit math shared by both verify layouts (runs
        inside the jitted verify program).

        logits [S, k+1, V] are the target's window scores: column j was
        computed from the token at position off+j, so it predicts the
        token at position off+j+1. Greedy: accept the longest prefix where
        the draft agrees with the target argmax; the emitted row IS the
        target argmax row, so greedy speculative output is bit-identical
        to sequential greedy decode. Sampled: standard leftover-
        distribution speculative sampling — accept d_i when
        u_i < p_t(d_i)/p_d(d_i) (u_i from the ACCEPT_SALT stream), resample
        a rejection column from normalize(max(p_t - p_d, 0)). The bonus /
        rejection column draws with the PLAIN request_key stream, so a
        fully-accepted window's bonus token — and every n_draft==0 row —
        emits the exact token a sequential decode step would have."""
        from .sampling import (ACCEPT_SALT, filtered_probs, request_key,
                               residual_sample, sample_tokens, spec_key)

        S = logits.shape[0]
        T = self.max_seq_len
        cols = jnp.arange(k + 1, dtype=jnp.int32)[None, :]       # [1, k+1]
        colk = jnp.arange(k, dtype=jnp.int32)[None, :]           # [1, k]
        in_window = colk < n_draft[:, None]                      # [S, k]
        tgt_greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if greedy_only:
            accept = (tgt_greedy[:, :k] == props) & in_window
            a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                        axis=1)                                  # [S]
            emit = tgt_greedy
        else:
            V = logits.shape[-1]
            t_rep = jnp.repeat(temps, k)
            k_rep = jnp.repeat(top_k, k)
            p_rep = jnp.repeat(top_p, k)
            p_t = filtered_probs(logits[:, :k].reshape((S * k, V)),
                                 t_rep, k_rep, p_rep).reshape((S, k, V))
            p_d = filtered_probs(dlogits_sk.reshape((S * k, V)),
                                 t_rep, k_rep, p_rep).reshape((S, k, V))
            pt_d = jnp.take_along_axis(p_t, props[..., None],
                                       axis=-1)[..., 0]          # [S, k]
            pd_d = jnp.take_along_axis(p_d, props[..., None],
                                       axis=-1)[..., 0]
            positions = (off[:, None] + 1 + colk).reshape(-1)    # [S*k]
            akeys = jax.vmap(spec_key, in_axes=(0, 0, None))(
                jnp.repeat(seeds, k), positions, ACCEPT_SALT)
            u = jax.vmap(jax.random.uniform)(akeys).reshape((S, k))
            ratio = pt_d / jnp.maximum(pd_d, 1e-38)
            exact = tgt_greedy[:, :k] == props
            accept = (jnp.where(temps[:, None] == 0.0, exact, u < ratio)
                      & in_window)
            a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                        axis=1)
            # column a's replacement token: greedy rows take the target
            # argmax; full-accept (and n_draft==0) rows sample the plain
            # per-position stream — the exact sequential draw — and
            # rejections take the residual distribution
            greedy_fix = jnp.take_along_axis(tgt_greedy, a[:, None],
                                             axis=1)[:, 0]
            La = jnp.take_along_axis(logits, a[:, None, None],
                                     axis=1)[:, 0]               # [S, V]
            rkeys = jax.vmap(request_key)(seeds, off + 1 + a)
            bonus_tok = sample_tokens(La, rkeys, temps, top_k, top_p)
            a_k = jnp.clip(a, 0, k - 1)
            pt_a = jnp.take_along_axis(p_t, a_k[:, None, None],
                                       axis=1)[:, 0]
            pd_a = jnp.take_along_axis(p_d, a_k[:, None, None],
                                       axis=1)[:, 0]
            resampled = residual_sample(rkeys, pt_a, pd_a)
            final_tok = jnp.where(
                temps == 0.0, greedy_fix,
                jnp.where(a >= n_draft, bonus_tok, resampled))
            props_pad = jnp.concatenate([props, props[:, -1:]], axis=1)
            emit = jnp.where(cols < a[:, None], props_pad,
                             final_tok[:, None])
        # commit: cut at the first emitted EOS, then the token budget —
        # the same order a sequential decode would stop in
        m_raw = a + 1
        is_eos = ((eos[:, None] != _NO_EOS) & (emit == eos[:, None])
                  & (cols < m_raw[:, None]))
        any_eos = jnp.any(is_eos, axis=1)
        m = jnp.where(any_eos, jnp.argmax(is_eos, axis=1) + 1, m_raw)
        m = jnp.minimum(m, remaining) * active.astype(jnp.int32)
        new_off = off + m
        last_emit = jnp.take_along_axis(
            emit, jnp.clip(m - 1, 0, k)[:, None], axis=1)[:, 0]
        new_tok = jnp.where(active, last_emit, tok)
        new_remaining = remaining - m
        hit_eos = active & (eos != _NO_EOS) & (new_tok == eos)
        new_active = (active & ~hit_eos & (new_remaining > 0)
                      & (new_off < T))
        return (new_off, new_tok, new_active, new_remaining, emit, m, a,
                hit_eos)

    def _build_verify(self, family: str, k: int):
        """The verify program, one executable per (sampling family, ladder
        rung k) on either layout: a draft scan proposes k tokens, then ONE
        [S, k+1] window forward through the target scores every proposal
        plus the bonus position, and the commit math accepts the longest
        agreeing prefix. The window's write mask is the active rows' columns
        up to their n_draft: on the paged layout later columns have no pages
        and go to the scratch page. Rejected rows need no cache surgery on
        the device — the offset rewind leaves them as inert stale rows
        (causal masking hides them, and they are rewritten before any query
        attends them, the same argument decode's idle-row tip writes rely
        on); the host truncates a paged slot's table past the accepted
        frontier."""
        import jax
        import jax.numpy as jnp

        from .sampling import DRAFT_SALT, sample_tokens, spec_key

        greedy_only = family == "greedy"
        kv, dkv = self._kv, self._dkv

        def verify(params, dparams, *args):
            cache, dcache, (off, tok, active, n_draft, temps, top_k, top_p,
                            eos, remaining, seeds) = _split(
                args, kv.n_args, dkv.n_args)

            def dstep(carry, i):
                dcache, cur = carry
                caches = dkv.views(dcache, off + i, active)
                h, caches, _ = self._backbone(
                    self.draft_model, dparams,
                    cur[:, None].astype(jnp.int64), caches)
                dlogits = self._head_traced(self.draft_model, dparams,
                                            h[:, 0])
                if greedy_only:
                    d = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                    out = d
                else:
                    keys = jax.vmap(spec_key, in_axes=(0, 0, None))(
                        seeds, off + i + 1, DRAFT_SALT)
                    d = sample_tokens(dlogits, keys, temps, top_k, top_p)
                    out = (d, dlogits)
                return (dkv.absorb(dcache, caches, active), d), out

            # k+1 steps, last proposal discarded: the extra step feeds d_k
            # so the draft cache stays dense through position off+k — a
            # fully-accepted window advances the frontier past off+k, and
            # a hole there would poison every later window's draft
            # attention (accept-rate collapse, not a correctness bug)
            (dcache, _), outs = jax.lax.scan(
                dstep, (dcache, tok), jnp.arange(k + 1, dtype=jnp.int32))
            if greedy_only:
                props = outs.T[:, :k]                            # [S, k]
                dlogits_sk = None
            else:
                props = outs[0].T[:, :k]
                dlogits_sk = jnp.moveaxis(outs[1], 0, 1)[:, :k]  # [S, k, V]

            win = jnp.concatenate([tok[:, None], props], axis=1)
            cols = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            caches = kv.views(
                cache, off, active[:, None] & (cols <= n_draft[:, None]))
            h, caches, _ = self._backbone(self.model, params,
                                          win.astype(jnp.int64), caches)
            S = win.shape[0]
            logits = self._head_traced(
                self.model, params, h.reshape((S * (k + 1), -1))
            ).reshape((S, k + 1, -1))
            cache = kv.absorb(cache, caches, active)
            (new_off, new_tok, new_active, new_remaining, emit, m, a,
             hit_eos) = self._spec_commit(
                jax, jnp, logits, dlogits_sk, props, off, tok, active,
                n_draft, temps, top_k, top_p, eos, remaining, seeds, k,
                greedy_only)
            return (*cache, *dcache, new_off, new_tok, new_active,
                    new_remaining, emit, m, a, hit_eos)

        return jax.jit(jax.named_scope("decode")(verify),
                       donate_argnums=self._donate(2, kv, dkv))

    def _spec_dispatch_rung(self) -> int:
        """Window rung for the next dispatch: the max ladder rung among
        active speculating slots, or 0 when the dispatch must fall back to
        plain decode. A cache that cannot mask a write (contiguous) falls back
        while any active slot sits on the last cache row — the window's
        unmasked per-row writes would collapse onto row T-1 and corrupt the
        position the bonus column reads (bounded: only the final token of a
        max-length sequence takes the slow path)."""
        import numpy as np

        if self.draft_model is None or not self._active.any():
            return 0
        rungs = self._spec_k[self._active]
        if not rungs.any():
            return 0
        if (not self._kv.masks_writes
                and int(self._offsets[self._active].max())
                >= self.max_seq_len - 1):
            return 0
        return int(rungs.max())

    def _advance_step(self) -> None:
        """One generation dispatch: the speculative verify program when
        any active slot opted in (non-spec slots ride along with a zero
        draft window and emit bit-identically to decode), plain decode
        otherwise."""
        k = self._spec_dispatch_rung()    # 0 while a chunk is in flight
        if k:
            self._verify_step(k)
        else:
            self._decode_step()

    def _verify_step(self, k: int) -> None:
        """Host driver for one speculative verify dispatch: draft scan +
        [S, k+1] target window + accept/commit on device, then per-slot
        token append, paged page-table truncation past the accepted
        frontier, and spec telemetry."""
        import jax.numpy as jnp
        import numpy as np

        from ..core import monitor

        family = ("greedy"
                  if not self._temps[self._active].any() else "sample")
        kv, dkv = self._kv, self._dkv
        label, donate = (f"serve.verify_{family}_k{k}",
                         self._donate(2, kv, dkv))
        entry = self._execs.get_or_build(
            ("serve.verify", family, k),
            lambda: self._build_verify(family, k),
            label=label, donate=donate, pin=True)
        # per-slot draft window: the request's rung, clamped so the window
        # never outruns the token budget (keeps paged writes inside the
        # admission reservation) or the cache end, and zero on non-spec
        # rows — which then emit exactly one sequentially-sampled token
        n_draft = np.minimum(self._spec_k,
                             np.maximum(self._remaining - 1, 0))
        n_draft = np.minimum(
            n_draft, np.maximum(self.max_seq_len - 2 - self._offsets, 0))
        n_draft = np.where(self._active, n_draft, 0).astype(np.int32)
        # cover every position the window may write, off..off+n_draft a
        # slot: n_draft is clamped to remaining-1 above, so this never
        # exceeds the admission reservation (end = off + remaining)
        kv.cover(self._active, self._offsets,
                 np.minimum(self._offsets + n_draft, self.max_seq_len - 1))
        call_args = (self._params, self._dparams, *kv.args(), *dkv.args(),
                     jnp.asarray(self._offsets), jnp.asarray(self._last_tok),
                     jnp.asarray(self._active), jnp.asarray(n_draft),
                     jnp.asarray(self._temps), jnp.asarray(self._topk),
                     jnp.asarray(self._topp), jnp.asarray(self._eos),
                     jnp.asarray(self._remaining), jnp.asarray(self._seeds))
        self._stash_exec(label, entry.fn, call_args, donate=donate)
        active_before = self._active.copy()
        p0 = self._execs.persistent_before(entry)
        t0 = time.perf_counter()
        try:
            cache, dcache, (off, tok, active, remaining, emit, m, a,
                            hits) = _split(entry(*call_args), kv.n_args,
                                           dkv.n_args)
            kv.take(cache, active_before)
            dkv.take(dcache, active_before)
            self._execs.note_compiles(
                entry, wall_s=time.perf_counter() - t0, persistent_before=p0,
                counter="serving.verify_compiles")
            self._offsets = np.array(off)
            self._last_tok = np.array(tok)
            self._active = np.array(active)
            self._remaining = np.array(remaining)
            self._carry = None
            emit = np.asarray(emit)                 # [S, k+1]
            m = np.asarray(m)
            a = np.asarray(a)
            hits = np.asarray(hits)
        except Exception as e:
            fr = _obs_flight.get()
            if fr is not None:
                fr.dump("serve_verify_exception",
                        {"step": self._steps, "family": family, "k": k,
                         "error": repr(e)})
            for slot in np.nonzero(self._active)[0]:
                req = self._slot_req[slot]
                if req is not None and req.done_ts is None:
                    self._finish(req, outcome="error")
            raise
        t1 = time.perf_counter()
        tr = _obs_tracer.get_tracer()
        if tr.enabled:
            tr.record_complete("serve.verify_step", t0, t1,
                               {"step": self._steps, "family": family,
                                "k": k})
        self._steps += 1
        now = time.perf_counter()
        mreg = _obs_metrics.active_registry()
        emitted = proposed = accepted = bonus = 0
        for slot in np.nonzero(active_before)[0]:
            req = self._slot_req[slot]
            ms = int(m[slot])
            for j in range(ms):
                req.tokens.append(int(emit[slot, j]))
            emitted += ms
            if req.first_token_ts is None:   # prefix-replay first token
                req.first_token_ts = now
            nd = int(n_draft[slot])
            acc = int(min(ms, int(a[slot])))
            bn = int(ms > int(a[slot]))
            req.spec_proposed += nd
            req.spec_accepted += acc
            req.spec_bonus += bn
            proposed += nd
            accepted += acc
            bonus += bn
            if nd and mreg is not None:
                mreg.histogram("spec.accept_rate",
                               boundaries=_OCCUPANCY_BUCKETS).observe(
                    acc / nd)
            # rollback: any page whose positions lie wholly past the
            # accepted frontier was only touched by rejected draft rows —
            # the cache drops it (always slot-private: shared prompt pages
            # sit below the frontier)
            kv.truncate(slot, int(self._offsets[slot]))
            if not self._active[slot]:
                req.finish_reason = "eos" if hits[slot] else "length"
                self._slot_req[slot] = None
                kv.release(slot)
                self._finish(req, now)
        self._count_tokens(emitted)
        monitor.stat("serving.steps").increase()
        monitor.stat("serving.verify_dispatches").increase()
        monitor.stat("serving.spec.proposed").increase(proposed)
        monitor.stat("serving.spec.accepted").increase(accepted)
        monitor.stat("serving.spec.bonus").increase(bonus)
        occupancy = float(active_before.mean())
        if mreg is not None:
            mreg.counter("serve.spec.proposed").inc(proposed)
            mreg.counter("serve.spec.accepted").inc(accepted)
            mreg.counter("serve.spec.bonus").inc(bonus)
            mreg.histogram("serve.decode_step_ms").observe((t1 - t0) * 1e3)
            mreg.histogram("serve.occupancy",
                           boundaries=_OCCUPANCY_BUCKETS).observe(occupancy)
            mreg.gauge("serve.queue_depth").set(len(self._queue))
            mreg.gauge("serve.active_slots").set(int(self._active.sum()))
            for name, value in kv.gauges().items():
                mreg.gauge("serve." + name).set(value)
        fr = _obs_flight.get()
        if self.sink is not None or fr is not None:
            rec = {
                "event": "serve_step", "step": self._steps,
                "ts": time.time(),
                # one target forward per verify dispatch — trace_summary
                # derives dispatches-per-token from this field
                "steps_per_dispatch": 1,
                "active_slots": int(active_before.sum()),
                "slot_count": self.slot_count,
                "occupancy": round(occupancy, 4),
                "queue_depth": len(self._queue),
                "tokens": emitted,
                "spec": True, "spec_window": k,
                "spec_proposed": proposed, "spec_accepted": accepted,
                "spec_bonus": bonus,
                **{name: round(v, 4) for name, v in kv.gauges().items()},
            }
            if self.sink is not None:
                self.sink.write(rec)
            if fr is not None:
                fr.record(rec)

    def _may_run_ahead(self) -> bool:
        """Whether the next chunk can be enqueued behind the one in flight
        before that one is fetched: only if the host knows already what the
        fetch will show of the slots. Every slot is live and stays live
        through the chunk in flight (its budget and its rows last longer
        than the chunk), so no slot frees for a queued request at that
        boundary; nobody speculates; admission is open (a draining engine
        takes today's loop to its end). An EOS is the one thing the host
        cannot foresee: the device masks that slot in the chunk enqueued
        ahead, which costs the slot's next request one chunk."""
        n = self.steps_per_dispatch
        if self._diffusion is not None:
            # a block needs at least two forwards (one that unmasks what is
            # left, one that commits), so a chunk of n forwards commits at
            # most ceil(n / 2) blocks a slot, whatever the schedule
            n = (n + 1) // 2 * self._diffusion.block_length
            return bool(not self._draining and self._active.all()
                        and int(self._remaining.min()) > n
                        and int(self._offsets.max()) + n
                        + self._diffusion.block_length <= self.max_seq_len)
        return bool(not self._draining and self._active.all()
                    and int(self._remaining.min()) > n
                    and int(self._offsets.max()) + n < self.max_seq_len
                    and self._spec_dispatch_rung() == 0)

    def _enqueue_decode(self, ahead: bool) -> dict:
        """Enqueue one decode chunk and return what `_decode_step` fetches
        later. `ahead`: the chunk in flight is not fetched yet, so the host's
        arrays are one chunk old; `_may_run_ahead` has made sure every slot
        moves `steps_per_dispatch` positions in it.

        Every call has the same form: the carry (`off`, `tok`, `active`,
        `remaining`) is the last chunk's own output, or made from the host's
        arrays after a seat changed them, and both are plain uncommitted
        arrays of the same types, so the decode entry keeps ONE executable a
        family. The per-slot constants go up once a seat."""
        import jax.numpy as jnp
        import numpy as np

        from ..core import monitor

        tr = _obs_tracer.get_tracer()
        kv = self._kv
        n_inner = self.steps_per_dispatch
        unseen = n_inner if ahead else 0
        # per-dispatch family pick: an all-greedy slot set runs the slim
        # executable; any sampling slot routes to the full one. Two decode
        # executables max, regardless of traffic mix.
        family = ("greedy"
                  if not self._temps[self._active].any() else "sample")
        # family's executable, arguments, and the call's return: the
        # device has the dispatch enqueued when this span ends
        with tr.boundary(
                "serve.decode.dispatch", family=family,
                step=self._steps + unseen,
                requests=[r.id for r in self._slot_req
                          if r is not None]) as dispatch:
            label, donate = f"serve.decode_{family}", self._donate(1, kv)
            build = (self._build_decode if self._diffusion is None
                     else self._build_block_decode)
            entry = self._execs.get_or_build(
                ("serve.decode", family), lambda: build(family),
                label=label, donate=donate, pin=True)
            # the positions this chunk may write (a slot's table row is
            # static within a dispatch)
            offsets = self._offsets + unseen
            kv.cover(self._active, offsets,
                     np.minimum(offsets + n_inner, self.max_seq_len) - 1)
            if self._carry is None:
                self._carry = tuple(jnp.asarray(a)
                                    for a in self._host_carry())
            if self._consts is None:
                self._consts = tuple(jnp.asarray(a)
                                     for a in self._host_consts())
            if self._diffusion is None:
                off, tok, active, remaining = self._carry
                temps, top_k, top_p, eos, seeds = self._consts
                call_args = (self._params, *kv.args(), off, tok, active,
                             temps, top_k, top_p, eos, remaining, seeds)
            else:
                call_args = (self._params, *kv.args(), *self._carry,
                             *self._consts)
            self._stash_exec(label, entry.fn, call_args, donate=donate)
            p0 = self._execs.persistent_before(entry)
            t0 = time.perf_counter()
            cache, (*outs, stats) = _split(entry(*call_args), kv.n_args)
            kv.take(cache, self._active)
            # the program's results: the carry, then what each step gave
            n_carry = len(self._carry)
            self._carry, outs = tuple(outs[:n_carry]), outs[n_carry:]
            self._execs.note_compiles(
                entry, wall_s=time.perf_counter() - t0,
                persistent_before=p0, counter="serving.decode_compiles")
        self._decode_dispatches += 1
        if ahead:
            self._decode_ahead += 1
            monitor.stat("serving.decode_ahead").increase()
        # the time the decode program had nothing enqueued: from the end of
        # the last dispatch's fetch to this dispatch's enqueue; none when
        # the chunk before this one was still in flight
        host_gap_ms = (0.0 if ahead else None if self._fetch_end is None
                       else (dispatch.t1 - self._fetch_end) * 1e3)
        return {"carry": self._carry, **dict(zip(self._out_names, outs)),
                "stats": stats, "family": family, "ahead": ahead,
                "dispatch_ms": dispatch.ms, "host_gap_ms": host_gap_ms}

    def _host_carry(self) -> tuple:
        """The host's copy of the decode program's carry, in its order."""
        return tuple(getattr(self, name) for name in self._carry_names)

    def _host_consts(self) -> tuple:
        """What the decode program is told of each slot's request, sent up
        once a seat, in the program's order."""
        return tuple(getattr(self, name) for name in self._const_names)

    def _decode_step(self) -> None:
        """Fetch and deliver one decode chunk: the one in flight, or one
        enqueued now. Before the fetch blocks, the next chunk is enqueued
        behind it on its device carry whenever `_may_run_ahead` says the
        fetch cannot change what that chunk has to be sent: the device then
        goes from one chunk to the next without waiting for the host. In any
        other state this is enqueue, fetch, deliver, and the next `step()`
        admits into the freed slots before it enqueues again."""
        import numpy as np

        tr = _obs_tracer.get_tracer()
        chunk, self._inflight = self._inflight, None
        try:
            if chunk is None:
                chunk = self._enqueue_decode(ahead=False)
            if self._may_run_ahead():
                self._inflight = self._enqueue_decode(ahead=True)
            with tr.boundary("serve.decode.fetch") as fetch:   # blocks
                # np.array (copy): zero-copy views of jax buffers are
                # read-only, and _admit mutates these in place when it
                # seats the next request
                for name, a in zip(self._carry_names, chunk["carry"]):
                    setattr(self, name, np.array(a))
                outs = {name: np.asarray(chunk[name])
                        for name in self._out_names}
                stats = {name: float(v)
                         for name, v in chunk["stats"].items()}
            self._fetch_end = fetch.t1
        except Exception as e:
            fr = _obs_flight.get()
            if fr is not None:
                fr.dump("serve_decode_exception",
                        {"step": self._steps,
                         "family": (chunk or {}).get("family"),
                         "error": repr(e)})
            # a failed decode dispatch takes every in-flight request with
            # it, and the chunk enqueued ahead: record each request as a
            # terminal error, once, before re-raising so the availability
            # SLI sees the blast radius
            self._inflight = self._carry = None
            for slot in np.nonzero(self._active)[0]:
                req = self._slot_req[slot]
                if req is not None and req.done_ts is None:
                    self._finish(req, outcome="error")
            raise
        spans_ms = {"admit": self._admit_ms,
                    "prefill_dispatch": [d for d, _ in self._prefill_ms],
                    "prefill_sync": [s for _, s in self._prefill_ms],
                    "decode_dispatch": chunk["dispatch_ms"],
                    "decode_fetch": fetch.ms}
        self._admit_ms, self._prefill_ms = None, []   # told once (drain()
        #                               dispatches without a step() before it)
        with tr.boundary("serve.emit") as emit:
            deliver = (self._emit_decoded if self._diffusion is None
                       else self._emit_block_decoded)
            deliver(**outs, spans_ms=spans_ms,
                    host_gap_ms=chunk["host_gap_ms"], emit=emit, stats=stats,
                    ahead=chunk["ahead"])
        if self._inflight is not None and not self._active.any():
            # every slot ended at an EOS: the chunk enqueued ahead ran with
            # all of them masked. Fetched now, so that an engine with no
            # live slot never has a chunk in flight
            self._decode_step()

    def _fold_step_stats(self, stats):
        """What the model reported at each fused step -> one value a
        dispatch, folded as `model.serving_step_stats` says ("mean" or
        "max"). Runs inside the decode program."""
        how = self.model.serving_step_stats
        return {name: v.max() if how[name] == "max" else v.mean()
                for name, v in stats.items()}

    def _emit_decoded(self, toks, was_active, hits, spans_ms, host_gap_ms,
                      emit, stats, ahead) -> None:
        """Hand a fetched dispatch's tokens to their requests, retire the
        finished, count, and write the `serve_step` sink record. `stats` is
        what the model reported of the dispatch (`_fold_step_stats`): it goes
        to `serving.<name>` counters (the last dispatch's value; `peak()`
        keeps the highest) and into the record."""
        import numpy as np

        n_inner = toks.shape[0]
        self._steps += n_inner
        now = time.perf_counter()
        for j in range(n_inner):
            alive_after = (was_active[j + 1] if j + 1 < n_inner
                           else self._active)
            for slot in np.nonzero(was_active[j])[0]:
                req = self._slot_req[slot]
                req.tokens.append(int(toks[j, slot]))
                if req.first_token_ts is None:   # prefix-replay first token
                    req.first_token_ts = now
                if not alive_after[slot]:     # retired at this inner step
                    req.finish_reason = "eos" if hits[j, slot] else "length"
                    self._slot_req[slot] = None
                    self._kv.release(slot)
                    self._finish(req, now)
        self._record_dispatch(
            was_active, int(was_active.sum()), spans_ms, host_gap_ms, emit,
            stats, ahead,
            # positions held by the slots still live after the dispatch:
            # what the next step's attention reads
            contexts=self._offsets[self._active].tolist())

    def _emit_block_decoded(self, blocks, was_active, commits, unmasked,
                            draws, confs, spans_ms, host_gap_ms, emit, stats,
                            ahead) -> None:
        """`_emit_decoded` for a dispatch of block steps: a forward in which
        a slot committed hands its request the block's tokens (past the
        given ones, cut to the budget and after an end token) where a token
        step hands it one. `blocks` [n_inner, S, B] are the blocks as each
        forward left them."""
        import numpy as np

        n_inner, emitted, now = blocks.shape[0], 0, time.perf_counter()
        B = self._diffusion.block_length
        self._steps += n_inner
        for j in range(n_inner):
            alive_after = (was_active[j + 1] if j + 1 < n_inner
                           else self._active)
            for slot in np.nonzero(was_active[j])[0]:
                req = self._slot_req[slot]
                if req.block_states is not None:
                    req.block_states.append({
                        "offset": req.block_offset,
                        "block": blocks[j, slot].tolist(),
                        "committed": bool(commits[j, slot]),
                        "draws": draws[j, slot].tolist(),
                        "confidences": confs[j, slot].tolist()})
                if not commits[j, slot]:
                    continue
                new = blocks[j, slot, req.given_in_block:].tolist()
                req.given_in_block = 0
                req.block_offset += B
                new = new[:req.max_new_tokens - len(req.tokens)]
                if req.eos_token_id is not None and req.eos_token_id in new:
                    new = new[:new.index(req.eos_token_id) + 1]
                    req.finish_reason = "eos"
                req.tokens.extend(new)
                emitted += len(new)
                if req.first_token_ts is None:
                    req.first_token_ts = now
                if not alive_after[slot]:     # retired at this forward
                    req.finish_reason = req.finish_reason or "length"
                    self._slot_req[slot] = None
                    self._kv.release(slot)
                    self._finish(req, now)
        from ..core import monitor

        counts = {"forwards": int(was_active.sum()),
                  "blocks_committed": int(commits.sum()),
                  "positions_unmasked": int(unmasked.sum())}
        self._forwards += counts["forwards"]
        self._blocks += counts["blocks_committed"]
        self._unmasked += counts["positions_unmasked"]
        self._block_tokens += emitted
        for name, n in counts.items():
            monitor.stat("serving." + name).increase(n)
        self._record_dispatch(
            was_active, emitted, spans_ms, host_gap_ms, emit, stats, ahead,
            **counts,
            # positions the live slots' next forward reads: what they hold
            # and the block's own
            contexts=(self._offsets[self._active] + B).tolist())

    def _record_dispatch(self, was_active, emitted: int, spans_ms,
                         host_gap_ms, emit, stats, ahead, **counts) -> None:
        """Count a delivered dispatch and write its `serve_step` sink
        record; `counts` are the fields only its kind of step has."""
        n_inner = was_active.shape[0]
        self._count_tokens(emitted)
        from ..core import monitor

        monitor.stat("serving.steps").increase(n_inner)
        for name, value in stats.items():
            monitor.stat("serving." + name).set(value)
        gauges = self._kv.gauges()
        if "state_bytes" in gauges:
            monitor.stat("serving.state_bytes").set(gauges["state_bytes"])
        occupancy = float(was_active.mean())
        mreg = _obs_metrics.active_registry()
        if mreg is not None:
            mreg.histogram("serve.decode_step_ms").observe(
                spans_ms["decode_dispatch"] + spans_ms["decode_fetch"])
            mreg.histogram("serve.occupancy",
                           boundaries=_OCCUPANCY_BUCKETS).observe(occupancy)
            mreg.gauge("serve.queue_depth").set(len(self._queue))
            mreg.gauge("serve.active_slots").set(int(self._active.sum()))
            for name, value in gauges.items():
                mreg.gauge("serve." + name).set(value)
        fr = _obs_flight.get()
        if self.sink is not None or fr is not None:
            # `emit` is still open: its time up to here, the record's own
            # write left out
            spans_ms["emit"] = (time.perf_counter() - emit.t0) * 1e3
            rec = {
                "event": "serve_step", "step": self._steps, "ts": time.time(),
                "steps_per_dispatch": n_inner,
                "active_slots": int(was_active[0].sum()),
                "slot_count": self.slot_count,
                # mean occupancy across the fused steps: retired slots are
                # masked (idle) until the chunk boundary
                "occupancy": round(occupancy, 4),
                "queue_depth": len(self._queue),
                "tokens": emitted,
                # host milliseconds of this dispatch's spans (serve.admit,
                # serve.prefill.*, serve.decode.*, serve.emit), and the gap
                # in which the decode program had nothing enqueued (None
                # for the engine's first dispatch)
                "spans_ms": spans_ms,
                "host_gap_ms": host_gap_ms,
                # enqueued while the dispatch before it was still in flight
                "ahead": ahead,
                **counts,
                **stats,
                **{name: round(v, 4) for name, v in gauges.items()},
            }
            if self.sink is not None:
                self.sink.write(rec)
            if fr is not None:
                fr.record(rec)

    # ---- bookkeeping ---------------------------------------------------
    def _count_tokens(self, n: int) -> None:
        if n:
            from ..core import monitor

            monitor.stat("serving.tokens").increase(n)

    def _finish(self, req: Request, now: Optional[float] = None,
                outcome: Optional[str] = None) -> None:
        from ..core import monitor

        req.done_ts = now if now is not None else time.perf_counter()
        # terminal disposition: normal completions inherit finish_reason
        # ("eos"/"length", "ok" as the fallback); abnormal exits pass
        # outcome="error"/"drained" explicitly and stay out of _completed
        req.outcome = outcome or req.outcome or req.finish_reason or "ok"
        if req.outcome not in ("error", "drained"):
            self._completed.append(req)
        monitor.stat("serving.requests").increase()
        monitor.stat("serving.outcome." + req.outcome).increase()
        tr = _obs_tracer.get_tracer()
        if tr.enabled:
            # the request's full span lifecycle: enqueue (instant at submit)
            # -> queue_wait -> prefill (both recorded at admit) -> decode ->
            # request envelope -> retire marker
            if req.first_token_ts is not None:
                tr.record_complete("serve.decode", req.first_token_ts,
                                   req.done_ts,
                                   req.trace_args(tokens=len(req.tokens)))
            tr.record_complete("serve.request", req.submit_ts, req.done_ts,
                               req.trace_args(finish=req.finish_reason))
            tr.instant("serve.retire", **req.trace_args(slot=req.slot))
        mreg = _obs_metrics.active_registry()
        if mreg is not None:
            mreg.counter("serve.requests").inc()
            if req.outcome == "error":
                mreg.counter("serve.errors").inc()
            if req.ttft_s is not None:
                mreg.histogram("serve.ttft_ms").observe(req.ttft_s * 1e3)
            if req.tpot_s is not None:
                mreg.histogram("serve.tpot_ms").observe(req.tpot_s * 1e3)
            if self.replica_name:
                pfx = f"serve.replica.{self.replica_name}."
                mreg.counter(pfx + "requests").inc()
                if req.outcome == "error":
                    mreg.counter(pfx + "errors").inc()
                if req.ttft_s is not None:
                    mreg.histogram(pfx + "ttft_ms").observe(req.ttft_s * 1e3)
        fr = _obs_flight.get()
        if self.sink is not None or fr is not None:
            wall = max(req.done_ts - req.submit_ts, 1e-9)
            rec = {
                "event": "serve_request", "request_id": req.id,
                "ts": time.time(),
                "prompt_len": int(len(req.prompt_ids)),
                "bucket": req.bucket, "slot": req.slot,
                "new_tokens": len(req.tokens),
                "finish_reason": req.finish_reason,
                "outcome": req.outcome,
                "ttft_s": (round(req.ttft_s, 6)
                           if req.ttft_s is not None else None),
                "queue_wait_s": (round(req.queue_wait_s, 6)
                                 if req.queue_wait_s is not None else None),
                "tpot_s": (round(req.tpot_s, 6)
                           if req.tpot_s is not None else None),
                "wall_s": round(wall, 6),
                "tokens_per_sec": round(len(req.tokens) / wall, 2),
                "queue_depth_at_submit": req.queue_depth_at_submit,
                "layout": self.kv_layout,
                "prefix_hit": req.prefix_hit,
                "shared_tokens": req.shared_tokens,
            }
            if req.speculate_k:
                rec["spec_k"] = req.speculate_k
                rec["spec_proposed"] = req.spec_proposed
                rec["spec_accepted"] = req.spec_accepted
                rec["spec_bonus"] = req.spec_bonus
            if req.tenant is not None:
                rec["tenant"] = req.tenant
            if req.trace_ctx is not None:
                rec["fleet_request_id"] = req.trace_ctx.request_id
            if self.sink is not None:
                self.sink.write(rec)
            if fr is not None:
                fr.record(rec)
