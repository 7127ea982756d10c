"""Auto-parallel planner: search hybrid topologies on the XLA cost model.

Reference parity: python/paddle/distributed/auto_parallel/planner.py (870 LoC
dist-attr search) + cost_model.py (802 LoC op-level cost simulation). The
TPU-native version is radically cheaper because the compiler IS the cost
model: for each legal hybrid topology we AOT-compile the fused train step
(`jit(...).lower().compile()` — no execution, no weights touched) and read

  - per-device HBM traffic   (cost_analysis()["bytes accessed"])
  - per-device peak memory   (memory_analysis(): args + temps + out - aliased)
  - interconnect volume      (collective output bytes parsed from the
                               optimized HLO — all-reduce/all-gather/
                               reduce-scatter/all-to-all/collective-permute)

and rank by a bandwidth-weighted time proxy. ICI bytes are weighted ~20x HBM
bytes (v5e: ~800 GB/s HBM vs ~45 GB/s/link ICI), the same ratio logic the
reference encodes in its CommOpCost tables (cost_model.py beta/alpha).

Candidates whose peak exceeds the per-device memory budget are rejected —
the planner's answer is then the cheapest FEASIBLE topology, which is how
ZeRO/mp configs win for models that do not fit replicated.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# bytes per element for HLO type tokens
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}

_COLL_RE = re.compile(
    r"=\s*(?P<type>.+?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_ARRAY_RE = re.compile(r"(?P<dt>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")


def _type_bytes(type_str: str) -> int:
    """Bytes of an HLO result type (array or tuple of arrays)."""
    total = 0
    for m in _ARRAY_RE.finditer(type_str):
        bpe = _DTYPE_BYTES.get(m.group("dt"))
        if bpe is None:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * bpe
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum collective OUTPUT bytes per op kind from optimized HLO text."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m and "-done" not in line.split("=")[0]:
            out[m.group("op")] = out.get(m.group("op"), 0) + \
                _type_bytes(m.group("type"))
    return out


@dataclass
class PlanResult:
    config: Dict[str, int]
    feasible: bool
    score: float                 # time proxy, lower is better
    hbm_bytes: int               # per-device bytes accessed
    ici_bytes: int               # per-device collective bytes
    peak_bytes: int              # per-device live memory estimate
    flops: float
    detail: Dict = field(default_factory=dict)


def factorizations(n: int, k: int) -> List[tuple]:
    """All k-tuples of power-of-2 (or residual) factors with product n —
    shared by the hybrid-config and mesh-shape planners."""
    if k == 1:
        return [(n,)]
    out = []
    d = 1
    while d <= n:
        if n % d == 0:
            out += [(d,) + r for r in factorizations(n // d, k - 1)]
        d *= 2
    return out


def enumerate_topologies(n_devices: int,
                         axes=("dp", "mp", "sharding"),
                         max_mp: Optional[int] = None) -> List[Dict[str, int]]:
    """All factorizations of n_devices over the given axes (reference
    planner's enumerate over process meshes, planner.py:plan)."""
    cands = []
    for shape in factorizations(n_devices, len(axes)):
        c = dict(zip(axes, shape))
        if max_mp and c.get("mp", 1) > max_mp:
            continue
        # hybrid_configs spells the sp axis "sep_degree" (reference naming).
        # dp_degree is ALWAYS explicit, even at 1: omitted, the HCG's
        # dp_degree=-1 auto-fill would grow dp to consume every host device,
        # silently scoring the candidate on a different topology than its
        # label (e.g. {'sep_degree': 4} becoming dp2 x sp4 on an 8-device
        # host when n_devices=4 was asked for)
        cand = {("sep_degree" if k == "sp" else f"{k}_degree"): v
                for k, v in c.items() if v > 1}
        cand["dp_degree"] = c.get("dp", 1)  # even when dp is not an axis
        cands.append(cand)
    # dedupe (dict order-insensitive)
    seen, uniq = set(), []
    for c in cands:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            uniq.append(c)
    return uniq


# v5e-flavored bandwidth ratio: one ICI byte costs ~20 HBM bytes of time
_ICI_WEIGHT = 20.0
# MXU flop per HBM byte at which compute and memory time break even (bf16
# v5e: 197e12 / 800e9 ≈ 250); used only to fold flops into the proxy
_FLOP_PER_BYTE = 250.0

# Stated resolution of the time-proxy model for single-chip variant ranking
# (fraction of predicted throughput). Grounded in the round-5 evidence:
# bench rows repeat within ~1.4% run-to-run, and the one confirmed
# structural mis-rank (b24 predicted over b16, measured 2.3% slower) sat on
# a predicted margin under 1% — the proxy scales bytes/flops ~linearly with
# batch, so batch-axis margins are structurally tiny while the real curve
# bends with per-step overhead and saturation. Margins inside this band are
# model noise, not signal (VERDICT r5 next #5).
PREDICTION_RESOLUTION = 0.03


def pair_verdict(pred_a, pred_b, batch_axis_only: bool,
                 resolution: float = PREDICTION_RESOLUTION):
    """Classify one predicted pairwise ranking: ("a" | "b" | "not_decidable",
    predicted margin). Batch-axis-only pairs (same program family, different
    batch size) are ABSTAINED inside `resolution` instead of ranked — the
    regime of the known b16/b24 mis-rank. Structurally different programs
    (remat, fused-CE chunk, topology changes) keep their full-margin
    ranking: their score deltas come from real byte/flop differences, not
    from the batch-linearity the model cannot resolve."""
    hi, lo = (pred_a, pred_b) if pred_a >= pred_b else (pred_b, pred_a)
    margin = (hi / lo - 1.0) if lo > 0 else float("inf")
    if batch_axis_only and margin < resolution:
        return "not_decidable", margin
    return ("a" if pred_a >= pred_b else "b"), margin


def score_compiled(comp) -> Dict:
    """Cost-model readout shared by the hybrid-config and mesh-shape
    planners: HBM traffic, ICI volume, peak memory, flops, time proxy."""
    from ...utils.hlo_inspect import cost_analysis_dict

    ca = cost_analysis_dict(comp)
    ma = comp.memory_analysis()
    coll = collective_bytes(comp.as_text())
    hbm = int(ca.get("bytes accessed", 0))
    ici = int(sum(coll.values()))
    flops = float(ca.get("flops", 0.0))
    peak = live = 0
    if ma is not None:
        live = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                   - ma.alias_size_in_bytes)
        peak = live + int(ma.temp_size_in_bytes)
    score = hbm + _ICI_WEIGHT * ici + flops / _FLOP_PER_BYTE
    return {"score": score, "hbm_bytes": hbm, "ici_bytes": ici,
            "peak_bytes": peak, "live_state_bytes": live, "flops": flops,
            "collectives": coll}


def saved_residual_bytes(f, *args) -> int:
    """Policy-aware autodiff residual bytes: what the backward pass will
    actually keep live between forward and backward, with jax.checkpoint
    policies APPLIED. This is the remat-sensitive peak component that XLA's
    AOT memory_analysis does not credit (it reported identical peaks with
    and without selective remat), so
    remat variants get distinct predicted peaks only through this term.
    Trace-level (jaxpr) analysis: nothing compiles or executes."""
    from jax._src.ad_checkpoint import saved_residuals

    res = saved_residuals(f, *args)
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a, _ in res if hasattr(a, "shape"))


def policy_peak_bytes(metrics: Dict, residual_bytes: int,
                      activation_shards: int = 1) -> int:
    """Remat-corrected peak estimate: persistent live state (params + opt +
    outputs - donation aliasing, from the compiled module) plus the
    policy-aware residuals (divided by the degree the batch/seq dims shard
    over — activations split across dp/sharding/sp shards, the residual
    trace is global). NOTE this omits the transient working set (the one
    checkpoint block's activations alive during its backward recompute);
    feasibility gating must pad it — score_topology uses
    _POLICY_GATE_SAFETY."""
    return int(metrics["live_state_bytes"]
               + residual_bytes // max(1, activation_shards))


# headroom multiplier when the policy peak (no transient working set) is
# allowed to override the XLA peak (no checkpoint-policy credit) in the
# feasibility gate: 2x covers the one-block recompute working set by a wide
# margin for deep models while still separating remat variants from plans
# that genuinely cannot fit
_POLICY_GATE_SAFETY = 2.0


def score_topology(model_factory: Callable, optimizer_factory: Callable,
                   sample_batch, config: Dict[str, int],
                   loss_fn=None, memory_budget: Optional[int] = None,
                   strategy_extra: Optional[Dict] = None) -> PlanResult:
    """AOT-compile the fused step under `config` and read the cost model.

    model_factory/optimizer_factory: fresh instances per candidate (engines
    bind per-topology shardings at construction).
    """
    from .. import DistributedStrategy
    from ..fleet import fleet as fleet_singleton
    from ..mesh import get_hybrid_communicate_group, \
        set_hybrid_communicate_group
    from ..engine import TrainStepEngine

    prev_hcg = get_hybrid_communicate_group()
    prev_fleet = (fleet_singleton._hcg, fleet_singleton._strategy,
                  fleet_singleton._is_initialized)
    try:
        set_hybrid_communicate_group(None)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = dict(config)
        if config.get("sharding_degree", 1) > 1:
            strategy.sharding = True
        for k, v in (strategy_extra or {}).items():
            setattr(strategy, k, v)
        fleet_singleton.init(is_collective=True, strategy=strategy)
        hcg = get_hybrid_communicate_group()

        model = model_factory()
        opt = optimizer_factory(model)
        eng = TrainStepEngine(model, opt, loss_fn=loss_fn, hcg=hcg,
                              strategy=strategy)
        arrays = [b._data if hasattr(b, "_data") else jnp.asarray(b)
                  for b in sample_batch]
        batch_axes = hcg.degrees["dp"] * hcg.degrees["sharding"]
        for a in arrays:
            if a.ndim >= 1 and a.shape[0] % batch_axes != 0:
                return PlanResult(config, False, float("inf"), 0, 0, 0, 0,
                                  {"reason": f"batch {a.shape[0]} % "
                                             f"dp*sharding {batch_axes} != 0"})
        jf = eng._build(arrays)
        comp = jf.lower(eng.params, eng.opt_state, jnp.float32(1e-3),
                        jnp.int32(1), jax.random.key(0), *arrays).compile()
        m = score_compiled(comp)
        # remat-corrected peak: XLA's AOT memory_analysis does not credit
        # jax.checkpoint policies (identical temp bytes with and without
        # selective remat), so recompute variants are additionally scored
        # by live state + policy-aware saved residuals. Feasibility takes
        # the MIN of the two estimates — but the policy estimate carries no
        # transient working set (the recompute-time block activations
        # saved_residuals cannot see), so the gate applies a 2x safety
        # factor to it before it may override the XLA number; a candidate
        # admitted that way is flagged speculative in detail. The residual
        # trace re-runs the whole forward, so it only happens when a
        # memory_budget makes feasibility a real question (plan_validate
        # computes its own peaks for reporting).
        peak_policy = gate_via = None
        peak_for_gate = m["peak_bytes"]
        if memory_budget is not None:
            try:
                act_shards = (hcg.degrees["dp"] * hcg.degrees["sharding"]
                              * hcg.degrees["sp"])
                res_b = saved_residual_bytes(eng.analysis_loss(*arrays),
                                             eng.params)
                peak_policy = policy_peak_bytes(m, res_b, act_shards)
                gated = int(_POLICY_GATE_SAFETY * peak_policy)
                if gated < peak_for_gate:
                    peak_for_gate = gated
                    gate_via = "policy_peak_with_safety"
            except Exception:
                pass  # analysis-only refinement: never fail the scoring
        feasible = memory_budget is None or peak_for_gate <= memory_budget
        return PlanResult(config, feasible, m["score"], m["hbm_bytes"],
                          m["ici_bytes"], m["peak_bytes"], m["flops"],
                          {"collectives": m["collectives"],
                           "peak_policy_bytes": peak_policy,
                           "feasibility_gate": gate_via})
    except Exception as e:  # infeasible lowering (e.g. indivisible shapes)
        return PlanResult(config, False, float("inf"), 0, 0, 0, 0,
                          {"reason": f"{type(e).__name__}: {e}"})
    finally:
        # restore BOTH topology globals: the module-level HCG and the Fleet
        # singleton (else fleet.get_hybrid_communicate_group() afterwards
        # describes the last scored candidate, not the user's config)
        set_hybrid_communicate_group(prev_hcg)
        (fleet_singleton._hcg, fleet_singleton._strategy,
         fleet_singleton._is_initialized) = prev_fleet


def plan(model_factory: Callable, optimizer_factory: Callable, sample_batch,
         n_devices: Optional[int] = None, loss_fn=None,
         memory_budget: Optional[int] = None, axes=("dp", "mp", "sharding"),
         verbose: bool = False) -> "tuple[Dict[str, int], List[PlanResult]]":
    """Pick the cheapest feasible hybrid topology for this model/batch.

    Returns (best_hybrid_configs, ranked results). Raises if nothing is
    feasible (memory budget too small for every topology).
    """
    n = n_devices or jax.device_count()
    results = [score_topology(model_factory, optimizer_factory, sample_batch,
                              c, loss_fn=loss_fn, memory_budget=memory_budget)
               for c in enumerate_topologies(n, axes=axes)]
    results.sort(key=lambda r: (not r.feasible, r.score))
    if verbose:
        for r in results:
            print(f"  {r.config}  feasible={r.feasible} "
                  f"score={r.score:.3e} hbm={r.hbm_bytes} ici={r.ici_bytes} "
                  f"peak={r.peak_bytes}")
    if not results or not results[0].feasible:
        reasons = {str(r.config): r.detail.get("reason", "over budget")
                   for r in results}
        raise RuntimeError(f"planner: no feasible topology: {reasons}")
    return results[0].config, results
