"""GPT family — the flagship model for the distributed benchmarks.

Reference analogue: the ERNIE/GPT models fleet's hybrid-parallel examples train
(hybrid_parallel_mp_layers.py / GPT-3 config in BASELINE.json). Built from the
meta_parallel TP layers so every parameter carries its PartitionSpec dist_attr —
under the TrainStepEngine pjit step this yields Megatron-style tensor parallelism
(column→row pairs, vocab-parallel embedding + loss) with GSPMD inserting the
collectives; dp/sharding/sp come from batch & optimizer-state shardings.

bf16-first: matmul inputs autocast under amp; layernorm/softmax/loss stay f32.
"""
from __future__ import annotations

import math

import jax

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.utils import recompute
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding,
)
from ..ops import creation as C
from ..ops import manipulation as P
from ..ops import slot_attention
from ..nn import functional as F
from ..nn.kv_cache import ChunkKV, KVLayerSpec


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
                 ffn_hidden_size=None, max_seq_len=1024, dropout=0.0,
                 attention_dropout=0.0, use_recompute=False,
                 recompute_granularity="full", dtype="float32",
                 tie_word_embeddings=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_hidden_size = ffn_hidden_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.attention_dropout = attention_dropout
        self.use_recompute = use_recompute
        # "full" | "selective" (reference recompute_configs granularity):
        # selective saves matmul outputs and recomputes only elementwise ops
        self.recompute_granularity = recompute_granularity
        self.dtype = dtype
        self.tie_word_embeddings = tie_word_embeddings


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
                     max_seq_len=128, **kw)


def gpt_345m(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24, num_heads=16,
                     max_seq_len=1024, **kw)


def gpt_1p3b(**kw):
    """GPT-3 1.3B (BASELINE config 4)."""
    return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
                     max_seq_len=2048, **kw)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_heads
        self.head_dim = config.hidden_size // config.num_heads
        self.hidden_size = config.hidden_size
        self.qkv_proj = ColumnParallelLinear(config.hidden_size, 3 * config.hidden_size,
                                             gather_output=False)
        self.out_proj = RowParallelLinear(config.hidden_size, config.hidden_size,
                                          input_is_parallel=True)
        self.attn_dropout = config.attention_dropout

    def forward(self, x, cache=None):
        # named scopes (here and below) are the fixed, unnumbered vocabulary
        # observability/device_trace.py reads device time by: they are
        # trace-time metadata and leave the compiled program as it was
        b, s = x.shape[0], x.shape[1]
        with jax.named_scope("qkv"):
            qkv = self.qkv_proj(x)  # [b, s, 3h] (h sharded over mp)
            qkv = P.reshape(qkv, (b, s, 3, self.num_heads, self.head_dim))
            q, k, v = P.unbind(qkv, axis=2)  # heads dim sharded over mp under pjit
        if cache is None:
            with jax.named_scope("core"):
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, dropout_p=self.attn_dropout,
                    training=self.training)
                out = P.reshape(out, (b, s, self.hidden_size))
            with jax.named_scope("out"):
                return self.out_proj(out)

        # KV-cache decode: `cache` is one layer's handle (nn/kv_cache.py). It
        # writes this chunk's rows and hands back what the queries may read
        # with the position each row holds; where the rows live is its
        # business. Fixed shapes throughout, so a whole decode loop is one
        # static-shape scan.
        with jax.named_scope("cache_write"):
            kc, vc, held, new_cache = cache.update(k._data, v._data)
        with jax.named_scope("core"):
            # a decode step over the slot cache reads each slot's rows to
            # its offset only, where a kernel can (ops/slot_attention.py)
            out = slot_attention.decode_core(q._data[:, :, :, None],
                                             new_cache)
            if out is not None:
                out = Tensor(out)
            else:
                qpos = cache.positions(s)                     # [b|1, s]
                mask = (held <= qpos[:, :, None])[:, None]    # [b|1, 1, s, T]
                out = F.scaled_dot_product_attention(
                    q, Tensor(kc), Tensor(vc), attn_mask=Tensor(mask),
                    dropout_p=0.0, training=False)
            out = P.reshape(out, (b, s, self.hidden_size))
        with jax.named_scope("out"):
            return self.out_proj(out), new_cache


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc1 = ColumnParallelLinear(config.hidden_size, config.ffn_hidden_size,
                                        gather_output=False)
        self.fc2 = RowParallelLinear(config.ffn_hidden_size, config.hidden_size,
                                     input_is_parallel=True)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(config.hidden_size)
        self.attn = GPTAttention(config)
        self.ln2 = nn.LayerNorm(config.hidden_size)
        self.mlp = GPTMLP(config)
        self.dropout = config.dropout
        self.use_recompute = config.use_recompute
        self.recompute_granularity = getattr(config, "recompute_granularity",
                                             "full")

    def _forward(self, x):
        # each scope takes the LayerNorm in front of it and its residual add
        with jax.named_scope("attn"):
            h = x + F.dropout(self.attn(self.ln1(x)), self.dropout,
                              training=self.training)
        with jax.named_scope("mlp"):
            return h + F.dropout(self.mlp(self.ln2(h)), self.dropout,
                                 training=self.training)

    def forward(self, x, cache=None):
        if cache is not None:
            with jax.named_scope("attn"):
                a, new_cache = self.attn(self.ln1(x), cache=cache)
                h = x + a
            with jax.named_scope("mlp"):
                return h + self.mlp(self.ln2(h)), new_cache
        if self.use_recompute and self.training:
            return recompute(self._forward, x,
                             policy=self.recompute_granularity)
        return self._forward(x)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_seq_len, config.hidden_size)
        self.drop = nn.Dropout(config.dropout)
        self.blocks = nn.LayerList([GPTBlock(config) for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size)

    def forward(self, input_ids, caches=None):
        with jax.named_scope("embed"):
            x = self._embed(input_ids, caches)
        if caches is not None:
            new_caches = []
            for blk, cache in zip(self.blocks, caches):
                x, c = blk(x, cache=cache)
                new_caches.append(c)
            with jax.named_scope("final_norm"):
                return self.ln_f(x), new_caches
        for blk in self.blocks:
            x = blk(x)
        with jax.named_scope("final_norm"):
            return self.ln_f(x)

    def _embed(self, input_ids, caches):
        s = input_ids.shape[1]
        if caches is not None:
            import jax.numpy as jnp

            pos = Tensor(caches[0].positions(s).astype(jnp.int64))
        else:
            pos = C.arange(0, s, dtype="int64")
        x = self.wte(input_ids) + self.wpe(pos)
        return self.drop(x)

    @staticmethod
    def fsdp_layer_key(name: str) -> str:
        """FSDP bucket granularity: one bucket per transformer block (the
        unit whose all-gather should hide under the previous block's
        matmuls), the token/position embeddings together, and everything
        else (final norm) in one tail bucket. Name-prefix based so it works
        for both GPTModel params and GPTForPretraining's 'gpt.'-qualified
        view of them."""
        import re

        m = re.match(r"(.*\bblocks\.\d+)\.", name)
        if m:
            return m.group(1)
        if ".wte." in name or ".wpe." in name or \
                name.startswith(("wte.", "wpe.")):
            return "embeddings"
        return "final"


class GPTForPretrainingPipe(nn.Layer):
    """Pipeline-parallel GPT (the reference's GPTForPretrainingPipe/PipelineLayer
    analogue, fleet/meta_parallel/pp_layers.py:159 + pipeline_parallel.py:31).

    The transformer body is stored as stacked per-stage parameters with leading dims
    [S, L/S, ...] where S = pp degree: the 'pp' mesh axis shards the stage dim, 'mp'
    shards the Megatron dims, and the body executes as an SPMD scan+ppermute pipeline
    (distributed/pipeline_schedule.py). Embedding / final-LN / loss are replicated over
    pp (computed identically on every pp rank — they are outside the bubble), matching
    the reference's shared-embedding stages without the p2p tie-grad allreduce.

    forward(input_ids, labels) -> scalar LM loss, same engine signature as
    GPTForPretraining; with pp degree 1 it degrades to a plain scan over all layers.
    """

    def __init__(self, config: GPTConfig, num_stages=None, num_microbatches=None,
                 num_virtual_stages=1):
        super().__init__()
        from jax.sharding import PartitionSpec as PS

        from ..distributed.mesh import get_hybrid_communicate_group
        from ..nn import initializer as I

        hcg = get_hybrid_communicate_group()
        self.config = config
        if config.dropout or config.attention_dropout:
            raise ValueError(
                "GPTForPretrainingPipe does not support dropout yet (needs per-stage "
                "RNG plumbing through the SPMD schedule); set dropout=0")
        self.num_stages = int(num_stages or (hcg.degrees["pp"] if hcg else 1))
        # interleaved (virtual-stage) 1F1B: each pp rank holds V chunks of
        # layers (logical stage v*P + r), cutting the pipeline bubble ~V-fold
        # (reference SectionWorker interleaving, device_worker.h:615)
        self.num_virtual_stages = int(num_virtual_stages)
        total_stages = self.num_stages * self.num_virtual_stages
        if config.num_layers % total_stages != 0:
            raise ValueError(
                f"num_layers {config.num_layers} not divisible by pp x virtual "
                f"= {self.num_stages} x {self.num_virtual_stages}")
        self.layers_per_stage = config.num_layers // total_stages
        self.num_microbatches = int(num_microbatches or max(1, self.num_stages))

        H, FF = config.hidden_size, config.ffn_hidden_size
        S, Lp, V = self.num_stages, self.layers_per_stage, self.num_virtual_stages
        self.wte = VocabParallelEmbedding(config.vocab_size, H)
        self.wpe = nn.Embedding(config.max_seq_len, H)
        self.ln_f = nn.LayerNorm(H)
        self.loss_fn = ParallelCrossEntropy()

        def mk(name, shape, spec, init):
            if V > 1 and len(spec) > 0 and spec[0] == "pp":
                # stage-stacked params only: leading dims [V, S], leaf
                # [v, r] = logical stage v*S + r, so P(None, "pp") places
                # each rank's V chunks where the interleaved schedule
                # executes them. Non-stage params (lm_head_w) keep their
                # shape.
                shape = (V,) + shape
                spec = PS(None, *spec)
            p = self.create_parameter(shape, default_initializer=init)
            p.dist_attr = spec
            self.add_parameter(name, p)

        w = I.Normal(std=0.02)
        zeros, ones = I.Constant(0.0), I.Constant(1.0)
        mk("qkv_w", (S, Lp, H, 3 * H), PS("pp", None, None, "mp"), w)
        mk("qkv_b", (S, Lp, 3 * H), PS("pp", None, "mp"), zeros)
        mk("proj_w", (S, Lp, H, H), PS("pp", None, "mp", None), w)
        mk("proj_b", (S, Lp, H), PS("pp"), zeros)
        mk("ln1_s", (S, Lp, H), PS("pp"), ones)
        mk("ln1_b", (S, Lp, H), PS("pp"), zeros)
        mk("ln2_s", (S, Lp, H), PS("pp"), ones)
        mk("ln2_b", (S, Lp, H), PS("pp"), zeros)
        mk("fc1_w", (S, Lp, H, FF), PS("pp", None, None, "mp"), w)
        mk("fc1_b", (S, Lp, FF), PS("pp", None, "mp"), zeros)
        mk("fc2_w", (S, Lp, FF, H), PS("pp", None, "mp", None), w)
        mk("fc2_b", (S, Lp, H), PS("pp"), zeros)
        if not config.tie_word_embeddings:
            mk("lm_head_w", (H, config.vocab_size), PS(None, "mp"), w)

    _STACKED = ("qkv_w", "qkv_b", "proj_w", "proj_b", "ln1_s", "ln1_b",
                "ln2_s", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
    _pipeline_stacked = True  # fleet.distributed_model pp-mode marker

    def forward(self, input_ids, labels=None):
        import jax
        import jax.numpy as jnp

        from ..core.dispatch import apply
        from ..distributed.mesh import get_hybrid_communicate_group
        from ..distributed.pipeline_schedule import (
            microbatch_merge, microbatch_split, spmd_pipeline)
        from ..jit import in_jit_trace

        cfg = self.config
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        s = input_ids.shape[1]
        pos = C.arange(0, s, dtype="int64")
        x = self.wte(input_ids) + self.wpe(pos)

        hcg = get_hybrid_communicate_group()
        use_spmd = (in_jit_trace() and hcg is not None
                    and hcg.degrees["pp"] == self.num_stages)
        mesh = hcg.mesh if use_spmd else None
        n_micro = self.num_microbatches

        use_recompute = cfg.use_recompute
        if use_recompute:
            from ..distributed.fleet.utils import _resolve_policy

            remat_policy = _resolve_policy(
                getattr(cfg, "recompute_granularity", "full"))

        V = self.num_virtual_stages

        def kernel(xa, *flat):
            params = dict(zip(self._STACKED, flat))
            def body(lp, h):
                def one(h, layer):
                    return _pipe_block_fwd(h, layer, nh, hd), None
                if use_recompute:  # recompute_interval analogue: checkpoint each block
                    one = jax.checkpoint(one, policy=remat_policy)
                h, _ = jax.lax.scan(one, h, lp)
                return h
            if mesh is not None:
                from ..distributed.pipeline_schedule import \
                    spmd_pipeline_interleaved

                mb = microbatch_split(xa, n_micro)
                if V > 1:
                    return microbatch_merge(spmd_pipeline_interleaved(
                        body, params, mb, mesh, "pp", num_chunks=V))
                return microbatch_merge(spmd_pipeline(body, params, mb, mesh, "pp"))
            # single-program fallback: same math, all stages scanned in
            # sequence (leading [V, S] or [S] dims flatten in logical-stage
            # order either way — chunk-major matches execution order)
            n_lead = 3 if V > 1 else 2
            merged = jax.tree.map(
                lambda l: l.reshape((math.prod(l.shape[:n_lead]),)
                                    + l.shape[n_lead:]), params)
            return body(merged, xa)

        h = apply("gpt_pipe_body", kernel, [x] + [getattr(self, n) for n in self._STACKED])
        h = self.ln_f(h)
        from ..ops import linalg as L
        from ..ops import reduction as R

        mp_deg = hcg.degrees["mp"] if hcg is not None else 1
        if labels is not None and cfg.tie_word_embeddings and mp_deg <= 1:
            # chunked fused LM loss (ops/fused.py), as in GPTForPretraining
            from ..ops.fused import fused_linear_cross_entropy

            loss = fused_linear_cross_entropy(h, self.wte.weight, labels,
                                              transpose_y=True,
                                              ignore_index=self.loss_fn.ignore_index)
            return R.mean(loss)
        if cfg.tie_word_embeddings:
            logits = L.matmul(h, self.wte.weight, transpose_y=True)
        else:
            logits = L.matmul(h, self.lm_head_w)
        if labels is None:
            return logits
        return R.mean(self.loss_fn(logits, labels))


def _pipe_block_fwd(x, p, nh, hd):
    """One transformer block in plain jnp (runs inside shard_map/scan).

    LayerNorm/softmax in f32, matmuls in the input dtype (bf16 under amp) — the same
    numerics as GPTBlock's ops-path forward.
    """
    import jax
    import jax.numpy as jnp

    def ln(h, scale, bias):
        hf = h.astype(jnp.float32)
        mu = jnp.mean(hf, -1, keepdims=True)
        var = jnp.mean(jnp.square(hf - mu), -1, keepdims=True)
        return ((hf - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias).astype(h.dtype)

    b, s, H = x.shape
    h = ln(x, p["ln1_s"], p["ln1_b"])
    qkv = h @ p["qkv_w"] + p["qkv_b"]
    qkv = qkv.reshape(b, s, 3, nh, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(mask[None, None], scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(b, s, nh * hd)
    x = x + o @ p["proj_w"] + p["proj_b"]
    h2 = ln(x, p["ln2_s"], p["ln2_b"])
    m = jax.nn.gelu(h2 @ p["fc1_w"] + p["fc1_b"], approximate=True)
    return x + m @ p["fc2_w"] + p["fc2_b"]


def _decode_exec_registry(model):
    """Per-model decode ExecutableRegistry (generate/generate_beam).

    One registry instance per model, keyed by the full sampling/shape tuple
    and bounded live by FLAGS_decode_jit_cache_size, so traffic cycling
    through sampling configs cannot grow the per-model store without bound.
    Legacy core.monitor counters ride as registry aliases:
    decode.jit_compiles (new executables), decode.cache_evictions (LRU
    drops)."""
    from ..core import flags as _flags
    from ..core.exec_registry import ExecutableRegistry

    reg = model.__dict__.get("_decode_exec_registry")
    if not isinstance(reg, ExecutableRegistry):
        reg = model.__dict__["_decode_exec_registry"] = ExecutableRegistry(
            name="gpt.decode",
            capacity=lambda: int(_flags.flag("decode_jit_cache_size")),
            miss_counter="decode.jit_compiles",
            eviction_counter="decode.cache_evictions")
    return reg


def _decode_jit_get(model, key, build):
    """Decode-executable lookup through the model's ExecutableRegistry; the
    label (key[0]) distinguishes greedy/sampled generate from beam search in
    registry telemetry."""
    reg = _decode_exec_registry(model)
    return reg.get_or_build(key, build, label=key[0]).fn


class GPTForPretraining(nn.Layer):
    """forward(input_ids, labels) -> scalar LM loss (the engine's expected signature)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config
        if config.tie_word_embeddings:
            self.lm_head = None  # reuse wte.weight (vocab-parallel)
        else:
            self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                                has_bias=False, gather_output=False)
        self.loss_fn = ParallelCrossEntropy()

    def logits(self, input_ids):
        return self._head_logits(self.gpt(input_ids))

    def forward(self, input_ids, labels=None):
        from ..ops import reduction as R

        h = self.gpt(input_ids)
        if labels is None:
            return self._head_logits(h)
        with jax.named_scope("lm_head_loss"):
            if self._can_fuse_loss():
                # chunked LM-head+CE (ops/fused.py): skips the [b, s, vocab]
                # f32 logits materialization — the dominant activation of
                # the step
                from ..ops.fused import fused_linear_cross_entropy

                loss = fused_linear_cross_entropy(
                    h, self.gpt.wte.weight, labels, transpose_y=True,
                    ignore_index=self.loss_fn.ignore_index)
            else:
                loss = self.loss_fn(self._head_logits(h), labels)
            return R.mean(loss)

    # param names here are 'gpt.blocks.N.*' / 'gpt.wte.*' / 'lm_head.*';
    # the prefix-insensitive key delegates cleanly
    fsdp_layer_key = staticmethod(GPTModel.fsdp_layer_key)

    def _can_fuse_loss(self):
        if self.lm_head is not None:
            return False
        from ..distributed.mesh import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        # vocab-sharded weight (mp > 1) keeps the vocab-parallel psum loss path
        return hcg is None or hcg.degrees["mp"] <= 1

    def _head_logits(self, h):
        """Hidden states -> vocab logits (shared by forward and decode)."""
        with jax.named_scope("lm_head"):
            if self.lm_head is None:
                from ..ops import linalg as L

                return L.matmul(h, self.gpt.wte.weight, transpose_y=True)
            return self.lm_head(h)

    # ---- what ServingEngine asks of a model (nn/kv_cache.py) -----------
    # a decode step reports nothing beside its tokens
    serving_step_stats = {}

    def serving_backbone(self):
        """(the layer called with (ids, caches=...), its prefix in
        state_dict)."""
        return self.gpt, "gpt."

    def kv_cache_spec(self, max_seq_len: int):
        """Every layer keeps every position of a slot."""
        cfg = self.config
        return [KVLayerSpec("full", max_seq_len, cfg.num_heads,
                            cfg.hidden_size // cfg.num_heads)
                ] * cfg.num_layers

    def decode_exec_registry(self):
        """This model's decode ExecutableRegistry (generate/generate_beam
        executables, LRU-bounded by FLAGS_decode_jit_cache_size). Public so
        benches/tests can inspect or clear the decode executable set."""
        return _decode_exec_registry(self)

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, top_p=1.0, eos_token_id=None, seed=0,
                 decode_strategy=None, num_beams=1, length_penalty=1.0,
                 prompt_bucket=None):
        """Autoregressive decode with KV cache — ONE jitted program: prefill
        fills fixed [b, total, nh, hd] cache buffers, then a lax.scan emits a
        token per step (static shapes end to end, the TPU-native decode loop).
        Greedy when temperature == 0; top-k/top-p nucleus sampling otherwise.
        After eos_token_id every subsequent position repeats eos.

        decode_strategy follows the reference generate() API: None picks
        greedy/sampling from temperature; "beam_search" (or num_beams > 1)
        routes to generate_beam.

        prompt_bucket (opt-in): an int target length or a ladder of rungs
        (e.g. serving.DEFAULT_LADDER) — the prompt is right-padded to the
        smallest rung >= its length and the executable is keyed on the RUNG,
        so every prompt length in a bucket shares one compiled program.
        Causal attention makes the pad harmless: logits are read at the last
        real position and decode resumes at offset=prompt_len, overwriting
        one pad cache row per generated token before it is ever attended —
        tokens are identical to the unpadded run.

        Single-replica inference path (mp decode would shard the head and
        psum logits; see PARITY row 49). Returns [b, prompt + max_new_tokens].
        """
        if decode_strategy not in (None, "greedy_search", "sampling",
                                   "beam_search"):
            raise ValueError(
                f"decode_strategy must be 'greedy_search', 'sampling' or "
                f"'beam_search', got {decode_strategy!r}")
        if decode_strategy == "beam_search" or (decode_strategy is None
                                                and num_beams > 1):
            if num_beams < 2:
                raise ValueError(
                    "beam_search needs num_beams >= 2 (reference generate() "
                    f"semantics), got {num_beams}")
            if prompt_bucket is not None:
                raise ValueError(
                    "prompt_bucket is not supported with beam_search")
            return self.generate_beam(
                input_ids, max_new_tokens=max_new_tokens,
                num_beams=int(num_beams),
                length_penalty=length_penalty, eos_token_id=eos_token_id)
        if num_beams > 1:
            raise ValueError(
                f"num_beams={num_beams} conflicts with "
                f"decode_strategy={decode_strategy!r}; use 'beam_search'")
        if decode_strategy == "greedy_search":
            temperature = 0.0
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        from ..jit import functional_call

        cfg = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
        orig_ids = ids
        b, prompt = ids.shape
        bucketed = prompt_bucket is not None
        if bucketed:
            from ..core.bucketing import resolve_bucket

            padded_len = resolve_bucket(prompt, prompt_bucket)
            ids = jnp.pad(ids, ((0, 0), (0, padded_len - prompt)))
        else:
            padded_len = prompt
        total = padded_len + max_new_tokens
        if total > cfg.max_seq_len:
            raise ValueError(f"prompt {padded_len}"
                             f"{' (bucketed)' if bucketed else ''} + "
                             f"max_new_tokens {max_new_tokens} exceeds "
                             f"max_seq_len {cfg.max_seq_len}")
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        state = self.state_dict(include_non_persistable_buffer=True)
        params = {k: v._data for k, v in state.items()}
        # KV cache dtype follows the autocast COMPUTE dtype of the attention
        # matmul (the op that reads the cache), not the param dtype: an f32
        # cache under bf16 amp would be converted to bf16 inside the decode
        # loop every step — 2 cache-sized casts per layer per token (~0.7
        # GB/step of pure HBM waste at the bench config; found by
        # tools/decode_hlo_probe.py). Routing through _autocast_dtype_for
        # keeps the white/black-list semantics: a user black-listing the
        # attention op to hold it in f32 keeps the f32 cache.
        from ..core.dispatch import _autocast_dtype_for, amp_ctx as _amp_ctx

        _amp = _amp_ctx()
        _mm_dtype = _autocast_dtype_for("attention", ())
        cache_dtype = (_mm_dtype if _mm_dtype is not None
                       else self.gpt.wte.weight._data.dtype)
        # Matmul-family weights are pre-cast to the autocast compute dtype
        # ONCE, outside the decode loop (weights-in-compute-dtype, the
        # standard inference layout). Relying on per-dispatch casts instead
        # leaves f32 masters in the loop: whether XLA hoists the casts is
        # backend-dependent, and un-hoisted they re-read ~2x the weight
        # bytes every token (the decode loop is weight-bandwidth-bound).
        # 1-D params (biases, norm scales) stay f32: the black-listed norm
        # ops want f32, and per-step casts of [h]-sized biases are noise.
        _w_dtype = _autocast_dtype_for("matmul", ())
        was_training = self.training
        self.eval()

        def sample(logits, key):
            if temperature == 0:
                return jnp.argmax(logits, axis=-1)
            logits = logits / jnp.float32(max(temperature, 1e-6))
            if top_k and top_k > 0:
                # clamp to vocab: top_k >= vocab must mean "keep everything",
                # not an out-of-range [:, -top_k] row index
                k_eff = min(int(top_k), logits.shape[-1])
                kth = jnp.sort(logits, axis=-1)[:, -k_eff][:, None]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if top_p < 1.0:
                sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(sorted_l, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                # smallest set with cumulative mass >= top_p
                cutoff_idx = jnp.sum(cum < top_p, axis=-1)
                cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], 1)
                logits = jnp.where(logits < cutoff, -jnp.inf, logits)
            return jax.random.categorical(key, logits, axis=-1)

        from ..core.autograd import no_grad
        from ..jit import _swapped_state, _tracing

        def head(params, h_arr):
            """last-position hidden -> logits, with weights from `params`."""
            with _swapped_state(self, params), _tracing(), no_grad():
                return self._head_logits(Tensor(h_arr))._data

        def run(params, ids, plen, key):
            if _w_dtype is not None:
                params = {k: (v.astype(_w_dtype)
                              if v.ndim >= 2 and jnp.issubdtype(
                                  v.dtype, jnp.floating)
                              else v)
                          for k, v in params.items()}
            # derive the submodule view from the TRACED params argument — a
            # closure over the concrete arrays would bake every weight into
            # the executable as a constant
            gpt_params = {k[len("gpt."):]: v for k, v in params.items()
                          if k.startswith("gpt.")}
            caches = [ChunkKV.zeros(b, total, nh, hd, cache_dtype)
                      for _ in range(cfg.num_layers)]
            h, caches = functional_call(self.gpt, gpt_params, Tensor(ids),
                                        caches=caches)
            if bucketed:
                # plen is a TRACED scalar: logits come from the last REAL
                # position and decode resumes at offset=plen, so one padded
                # executable serves every prompt length in the bucket. Each
                # generated token overwrites one pad cache row before it is
                # ever attended (causal mask) — numerics match unpadded.
                last_h = jax.lax.dynamic_index_in_dim(h._data, plen - 1, 1,
                                                      keepdims=False)
                caches = [c.rewound(plen) for c in caches]
            else:
                last_h = h._data[:, -1]
            logits = head(params, last_h)
            key, sub = jax.random.split(key)
            tok = sample(logits, sub).astype(ids.dtype)
            done = (jnp.zeros((b,), bool) if eos_token_id is None
                    else tok == eos_token_id)

            def step(carry, _):
                caches, tok, key, done = carry
                h, caches = functional_call(self.gpt, gpt_params,
                                            Tensor(tok[:, None]),
                                            caches=caches)
                logits = head(params, h._data[:, 0])
                key, sub = jax.random.split(key)
                nxt = sample(logits, sub).astype(tok.dtype)
                if eos_token_id is not None:
                    nxt = jnp.where(done, eos_token_id, nxt)
                    done = done | (nxt == eos_token_id)
                return (caches, nxt, key, done), nxt

            if max_new_tokens > 1:
                _, toks = jax.lax.scan(step, (caches, tok, key, done), None,
                                       length=max_new_tokens - 1)
                out = jnp.concatenate([ids, tok[:, None], toks.T], axis=1)
            else:
                out = jnp.concatenate([ids, tok[:, None]], axis=1)
            return out

        try:
            # one compiled decode program per sampling configuration — a fresh
            # jax.jit wrapper each call would recompile every generate().
            # The active amp scope is part of the key: tracing under
            # paddle.amp.auto_cast() bakes bf16 matmuls into the executable
            # (halves decode weight traffic — the decode loop is HBM-bound)
            amp = _amp  # the scope captured above (cache_dtype reads it too)
            # the FULL behavioral tuple: dtype/level AND the op lists that
            # _autocast_dtype_for consults — scopes differing only in
            # white/black lists must not share an executable
            amp_key = ((str(amp.dtype), amp.level, frozenset(amp.white),
                        frozenset(amp.black)) if amp is not None else None)
            # cache_dtype is baked into run()'s closure: key it, or a later
            # call on the no-amp fallback path (param dtype changed, amp_key
            # identical) would retrace the stale closure. Bucketed keys use
            # the RUNG, not the prompt length — the whole bucket shares one
            # executable (plen stays a traced argument).
            cache_key = ("gpt.generate", b, padded_len, bucketed,
                         max_new_tokens, float(temperature), int(top_k),
                         float(top_p), eos_token_id, amp_key,
                         str(cache_dtype))
            fn = _decode_jit_get(self, cache_key, lambda: jax.jit(run))
            out = fn(params, ids, jnp.int32(prompt), jax.random.key(seed))
            if bucketed:
                # reassemble outside the jit: echo the UNPADDED prompt, then
                # the generated tokens (which sit after the padded region) —
                # slicing inside the executable would re-specialize per
                # prompt length and defeat the bucket
                out = jnp.concatenate([orig_ids, out[:, padded_len:]], axis=1)
        finally:
            if was_training:
                self.train()
        return Tensor(out)

    def generate_beam(self, input_ids, max_new_tokens=32, num_beams=4,
                      length_penalty=1.0, eos_token_id=None):
        """Beam-search decode as ONE jitted program (the reference's
        BeamSearchDecoder / beam_search_op machinery, python/paddle's
        generate(decode_strategy="beam_search"), re-designed TPU-native):
        the KV cache carries a beam dim [b*K, total, nh, hd], each scan step
        log-softmaxes all beams' logits, takes top-K over the flattened
        [K*V] continuations, and REORDERS the cache by gathering beam rows —
        static shapes end to end, no host round-trips. Finished beams emit a
        forced eos with log-prob 0 so their score freezes. Returns the best
        beam per batch row, [b, prompt + max_new_tokens], ranked by
        score / length**length_penalty (GNMT-style).
        """
        import jax
        import jax.numpy as jnp

        from ..core.autograd import no_grad
        from ..core.dispatch import _autocast_dtype_for, amp_ctx as _amp_ctx
        from ..core.tensor import Tensor
        from ..jit import _swapped_state, _tracing, functional_call

        cfg = self.config
        K = int(num_beams)
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, prompt = ids.shape
        total = prompt + max_new_tokens
        if total > cfg.max_seq_len:
            raise ValueError(f"prompt {prompt} + max_new_tokens "
                             f"{max_new_tokens} exceeds max_seq_len "
                             f"{cfg.max_seq_len}")
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        state = self.state_dict(include_non_persistable_buffer=True)
        params = {k: v._data for k, v in state.items()}
        _amp = _amp_ctx()
        _mm_dtype = _autocast_dtype_for("attention", ())
        cache_dtype = (_mm_dtype if _mm_dtype is not None
                       else self.gpt.wte.weight._data.dtype)
        _w_dtype = _autocast_dtype_for("matmul", ())
        was_training = self.training
        self.eval()
        NEG = jnp.float32(-1e30)

        def head(params, h_arr):
            with _swapped_state(self, params), _tracing(), no_grad():
                return self._head_logits(Tensor(h_arr))._data

        def run(params, ids):
            if _w_dtype is not None:
                params = {k: (v.astype(_w_dtype)
                              if v.ndim >= 2 and jnp.issubdtype(
                                  v.dtype, jnp.floating) else v)
                          for k, v in params.items()}
            gpt_params = {k[len("gpt."):]: v for k, v in params.items()
                          if k.startswith("gpt.")}
            # ---- prefill on the raw batch, then tile everything to beams
            caches = [ChunkKV.zeros(b, total, nh, hd, cache_dtype)
                      for _ in range(cfg.num_layers)]
            h, caches = functional_call(self.gpt, gpt_params, Tensor(ids),
                                        caches=caches)
            logp0 = jax.nn.log_softmax(
                head(params, h._data[:, -1]).astype(jnp.float32), axis=-1)
            vocab = logp0.shape[-1]
            scores, tok0 = jax.lax.top_k(logp0, K)        # [b, K] each
            toks = jnp.zeros((b, K, max_new_tokens), jnp.int32)
            toks = toks.at[:, :, 0].set(tok0)
            finished = (jnp.zeros((b, K), bool) if eos_token_id is None
                        else tok0 == eos_token_id)
            lengths = jnp.ones((b, K), jnp.float32)  # emitted per beam

            def per_beam(caches, reorder):
                """`reorder` on every leaf that has a batch axis (the scalar
                offset has none)."""
                return jax.tree_util.tree_map(
                    lambda a: a if a.ndim == 0 else reorder(a), caches)

            # row i -> beams i*K..i*K+K-1
            caches = per_beam(caches, lambda a: jnp.repeat(a, K, axis=0))

            def step(carry, t):
                caches, toks, scores, finished, lengths = carry
                # each beam continues from its last emitted token
                prev = jnp.reshape(
                    jax.lax.dynamic_index_in_dim(
                        jnp.moveaxis(toks, 2, 0), t - 1, 0, keepdims=False),
                    (b * K,))
                h, caches = functional_call(self.gpt, gpt_params,
                                            Tensor(prev[:, None]),
                                            caches=caches)
                logp = jax.nn.log_softmax(
                    head(params, h._data[:, 0]).astype(jnp.float32), axis=-1)
                logp = jnp.reshape(logp, (b, K, vocab))
                if eos_token_id is not None:
                    # finished beams: only "emit eos again, score unchanged"
                    onehot = jnp.where(
                        jnp.arange(vocab)[None, None, :] == eos_token_id,
                        jnp.float32(0), NEG)
                    logp = jnp.where(finished[..., None], onehot, logp)
                cand = scores[..., None] + logp            # [b, K, V]
                flat_cand = jnp.reshape(cand, (b, K * vocab))
                scores, idx = jax.lax.top_k(flat_cand, K)  # [b, K]
                beam_idx = idx // vocab                    # [b, K]
                token = (idx % vocab).astype(jnp.int32)
                # reorder beam state by gathered parent index
                toks = jnp.take_along_axis(toks, beam_idx[..., None], axis=1)
                toks = toks.at[:, :, t].set(token)
                fin_g = jnp.take_along_axis(finished, beam_idx, axis=1)
                len_g = jnp.take_along_axis(lengths, beam_idx, axis=1)
                lengths = jnp.where(fin_g, len_g, len_g + 1.0)
                finished = fin_g if eos_token_id is None else \
                    fin_g | (token == eos_token_id)
                # the functional_call appended this step's K/V for the OLD
                # beam order; gather AFTER the append so each child inherits
                # its parent's cache including the new row
                rows = (jnp.arange(b)[:, None] * K + beam_idx).reshape(-1)
                caches = per_beam(caches, lambda a: a[rows])
                return (caches, toks, scores, finished, lengths), None

            if max_new_tokens > 1:
                (caches, toks, scores, finished, lengths), _ = jax.lax.scan(
                    step, (caches, toks, scores, finished, lengths),
                    jnp.arange(1, max_new_tokens))
            # GNMT length penalty; pick the best beam per row
            norm = scores / jnp.power(lengths, jnp.float32(length_penalty))
            best = jnp.argmax(norm, axis=1)                # [b]
            best_toks = jnp.take_along_axis(
                toks, best[:, None, None], axis=1)[:, 0]   # [b, max_new]
            if eos_token_id is not None:
                # positions after the eos repeat eos (matches generate())
                emitted = jnp.cumsum(
                    (best_toks == eos_token_id).astype(jnp.int32), axis=1)
                seen = (emitted - (best_toks == eos_token_id)) > 0
                best_toks = jnp.where(seen, eos_token_id, best_toks)
            return jnp.concatenate([ids, best_toks.astype(ids.dtype)], axis=1)

        try:
            amp = _amp
            amp_key = ((str(amp.dtype), amp.level, frozenset(amp.white),
                        frozenset(amp.black)) if amp is not None else None)
            cache_key = ("gpt.generate_beam", b, prompt, max_new_tokens, K,
                         float(length_penalty), eos_token_id, amp_key,
                         str(cache_dtype))
            fn = _decode_jit_get(self, cache_key, lambda: jax.jit(run))
            out = fn(params, ids)
        finally:
            if was_training:
                self.train()
        return Tensor(out)
