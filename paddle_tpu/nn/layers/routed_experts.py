"""A routed-expert feed-forward layer that can run a published model:
dropless top-k routing over all experts, rows sorted by expert, the experts
held here as one grouped matmul.

The layer is told which experts it holds (`first`, `count` of the published
`num_experts`). It scores and chooses over ALL of them and computes the part
of the result its own experts give; with `count == num_experts` that is the
whole result, and the parts of disjoint shares add up to it. Nothing stands
in for the experts that live elsewhere or for their traffic: on one chip the
layer runs without its exchange.

Routing, in float32: scores `s = sigmoid(m Wr)` (`score_func="sigmoid"`, the
`afmoe` and DeepSeek-V3 routers) or `s = softmax(m Wr)` over all experts
(`"softmax"`, DeepSeek-V2's). With `n_group` > 1 the choice is group-limited:
the experts lie in `n_group` equal groups in order, a group's score is the
best of its experts' (with the bias), the `topk_group` best groups stay and
every other group's experts score 0. The k experts are `top_k(s + bias)` of
what is left, where `bias` is the load-balancing buffer, which chooses and
does not weigh; the weights are `s[chosen]`, divided by their sum if
`route_norm`, times `route_scale` (DeepSeek-V2: not normalised, times 16).
Every token gets its k experts: no capacity, nothing dropped.

The load a step reports: `moe_touched` counts the experts of ALL the
published `num_experts` that received a row, whoever holds them;
`touched_held` counts those among the `count` held here, which is what sets
the expert bytes this chip reads. They are equal where a layer holds all.

`distributed/meta_parallel/moe.py` (GShard's one-hot dispatch with a
capacity) stays for the dryrun that uses it; a `[tokens, experts, capacity]`
tensor does not exist at 128 experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...observability import metrics
from .. import initializer as I
from ..layer import Layer


class NormalInto(I.Initializer):
    """Normal(0, std) drawn in float32 and rounded to the parameter's dtype
    inside one compiled call, so that a bf16 parameter never has a float32
    twin on the device."""

    def __init__(self, std: float = 0.02):
        self.std = float(std)

    def __call__(self, shape, dtype):
        from ...core import dtype as dtypes
        from ...core import random as random_mod

        key = random_mod.named_generator("init").next_key()
        return _draw(key, tuple(shape), self.std, dtypes.convert_dtype(dtype))


def _draw_impl(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


_draw = jax.jit(_draw_impl, static_argnums=(1, 2, 3))


class _ExpertWeights(Layer):
    """The stacked SwiGLU weights of the experts held: `w_gate`, `w_up`
    [count, hidden, width], `w_down` [count, width, hidden]."""

    def __init__(self, count, hidden, width, dtype, std):
        super().__init__(dtype=dtype)
        init = NormalInto(std)
        self.w_gate = self.create_parameter((count, hidden, width),
                                            default_initializer=init)
        self.w_up = self.create_parameter((count, hidden, width),
                                          default_initializer=init)
        self.w_down = self.create_parameter((count, width, hidden),
                                            default_initializer=init)


class _Router(Layer):
    def __init__(self, hidden, num_experts, std):
        super().__init__(dtype="float32")
        self.weight = self.create_parameter((hidden, num_experts),
                                            default_initializer=NormalInto(std))


def _swiglu(xs, ex, sizes, num_experts):
    """The held experts' SwiGLU over `xs` sorted by expert: one Pallas call
    where `grouped_experts.takes_kernel` allows (a band of mean rows an
    expert, on a TPU, in a single-device program), else three `ragged_dot`
    calls. `moe.calls.ragged_dot` / `moe.calls.grouped_kernel` count the
    forms traced."""
    # imported at the call: the kernels' toolkit takes over a second
    from ...ops.pallas import grouped_experts as ge
    w = (ex.w_gate._data, ex.w_up._data, ex.w_down._data)
    rows, hidden = xs.shape
    form = ("grouped_kernel" if ge.takes_kernel(
        rows, num_experts, hidden, w[0].shape[2], xs.dtype, w[0].dtype)
        else "ragged_dot")
    metrics.default_registry().counter(
        "moe.calls." + form, "routed experts' SwiGLU traced, by form").inc()
    return (ge.swiglu if form == "grouped_kernel" else ge.plain)(
        xs, *w, sizes)


class RoutedExperts(Layer):
    """forward(x [..., hidden]) -> the held experts' part of the layer's
    result, same shape and dtype. `routed(m)` also returns the step's load:
    (experts of all `num_experts` that received a row, the most rows one
    expert received); `routed_load(m)` returns the rows each of all experts
    received instead, from which `touched_held` counts the experts held
    here that received one."""

    def __init__(self, hidden_size, expert_width, num_experts, top_k,
                 first=0, count=None, route_norm=True, route_scale=1.0,
                 bias_std=0.0, dtype="float32", init_std=0.02,
                 score_func="sigmoid", n_group=1, topk_group=1):
        super().__init__(dtype=dtype)
        if score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func {score_func!r}: the router scores "
                             f"with 'sigmoid' or 'softmax'")
        if num_experts % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(f"{num_experts} experts in {n_group} groups, "
                             f"{topk_group} of them kept")
        if top_k > num_experts // n_group * topk_group:
            raise ValueError(f"top_k {top_k} of {topk_group} groups of "
                             f"{num_experts // n_group} experts")
        self.score_func = score_func
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        count = num_experts - first if count is None else int(count)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"experts held ({first}, {count}) do not lie in "
                             f"the {num_experts} published")
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.first, self.count = int(first), count
        self.route_norm, self.route_scale = bool(route_norm), float(route_scale)
        self.router = _Router(hidden_size, num_experts, init_std)
        bias = NormalInto(bias_std)((num_experts,), "float32") if bias_std \
            else jnp.zeros((num_experts,), jnp.float32)
        self.register_buffer("expert_bias", Tensor(bias))
        self.experts = _ExpertWeights(count, hidden_size, expert_width, dtype,
                                      init_std)

    def route(self, m):
        """m [rows, hidden] -> (chosen [rows, k] int32, weights [rows, k]
        float32). The matmul and the scores are float32 at the highest
        precision, as in the reference: it is 0.3% of the layer's work and
        removes most of the choices that rounding would flip."""
        logits = jnp.dot(m.astype(jnp.float32), self.router.weight._data,
                         precision=jax.lax.Precision.HIGHEST)
        if self.score_func == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            scores = jax.nn.sigmoid(logits)
        choose = scores + self.expert_bias._data
        if self.n_group > 1:
            groups = choose.reshape(choose.shape[0], self.n_group, -1)
            _, kept = jax.lax.top_k(groups.max(-1), self.topk_group)
            stays = jnp.zeros(groups.shape[:2], bool).at[
                jnp.arange(groups.shape[0])[:, None], kept].set(True)
            choose = jnp.where(stays[:, :, None], groups, 0.0).reshape(
                choose.shape)
        _, chosen = jax.lax.top_k(choose, self.top_k)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.route_norm:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), w * self.route_scale

    def routed(self, m):
        """m [rows, hidden] -> (out [rows, hidden], touched, max_load)."""
        out, load = self.routed_load(m)
        return (out, *self.load_stats(load))

    @staticmethod
    def load_stats(load):
        """(experts of all that received a row, the most rows one received)
        of `load` as `routed_load` returns it."""
        return (load > 0).sum().astype(jnp.float32), load.max()

    def touched_held(self, load):
        """Of the experts held here, how many received a row (`load` as
        `routed_load` returns it)."""
        held = jax.lax.dynamic_slice_in_dim(load, self.first, self.count)
        return (held > 0).sum().astype(jnp.float32)

    def routed_load(self, m):
        """m [rows, hidden] -> (out [rows, hidden], load [num_experts]: the
        rows each published expert received)."""
        rows, k = m.shape[0], self.top_k
        ex = self.experts
        with jax.named_scope("router"):
            chosen, w = self.route(m)
        with jax.named_scope("dispatch"):
            expert = chosen.reshape(-1)                        # [rows * k]
            load = jnp.bincount(expert, length=self.num_experts)
            local = expert - self.first
            held = (local >= 0) & (local < self.count)
            # rows of experts held elsewhere sort to the end, past every group
            order = jnp.argsort(jnp.where(held, local, self.count),
                                stable=True)
            sizes = jax.lax.dynamic_slice_in_dim(load, self.first, self.count)
            xs = jnp.take(m, order // k, axis=0)               # [rows * k, h]
        with jax.named_scope("experts"):
            y = _swiglu(xs, ex, sizes.astype(jnp.int32), self.num_experts)
        with jax.named_scope("combine"):
            # back to token order, then the weighted sum of a token's k rows
            y = jnp.take(y, jnp.argsort(order), axis=0).reshape(rows, k, -1)
            mine = held.reshape(rows, k, 1)
            y = jnp.where(mine, y.astype(jnp.float32) * w[..., None], 0.0)
            out = y.sum(1).astype(m.dtype)
        return out, load

    def forward(self, x):
        data = x._data if isinstance(x, Tensor) else x
        out, _, _ = self.routed(data.reshape(-1, data.shape[-1]).astype(
            self.experts.w_gate._data.dtype))
        out = out.reshape(data.shape).astype(data.dtype)
        return Tensor(out) if isinstance(x, Tensor) else out
