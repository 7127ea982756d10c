"""Ring vs Ulysses vs dense sequence parallelism — XLA cost-model comparison.

An on-chip ring-vs-Ulysses sweep needs multiple real chips (sp>1 on one chip
is degenerate). This is the chip-independent half: compile the FULL GPT train step at each (impl,
sp_degree, seq) on the virtual 8-device CPU mesh and report what the XLA
cost model and the compiled HLO say —

  flops            cost_analysis() total flops (per device program)
  bytes            cost_analysis() bytes accessed (HBM traffic proxy)
  peak_mb          memory_analysis() temp+output peak per device
  collective ops   collective-permute (ring) / all-to-all (Ulysses) counts

Ring should show collective-permutes with per-shard peak memory ~1/sp of
dense attention's; Ulysses shows all-to-alls with head-sharded compute.
Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     JAX_PLATFORMS=cpu python tools/sp_cost_compare.py
One JSON line per config.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401  (checkout-hermetic sys.path, tools/_bootstrap.py)

import argparse
import json
import re


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="1024,4096")
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--flash", action="store_true",
                    help="compile with the Pallas flash kernel (interpret "
                         "mode on CPU): the linear-memory attention that "
                         "long-context configs actually run with")
    ap.add_argument("--sp-degrees", default="1,2,4",
                    help="sp degrees to sweep (1 = dense baseline)")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-chip HBM budget used for the feasible column "
                         "(v5e: 16 GB)")
    args = ap.parse_args()

    import os
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=8"
                                   ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    if args.flash:
        paddle.set_flags({"use_flash_attention": True,
                          "pallas_interpret_ok": True})

    degrees = [int(d) for d in args.sp_degrees.split(",")]
    bad = [d for d in degrees if d < 1 or 8 % d]
    if bad:
        ap.error(f"--sp-degrees must divide the 8-device mesh, got {bad}")
    combos = []
    for sp in degrees:
        if sp == 1:
            combos.append(("dense", 1))
        else:
            combos.append(("ring", sp))
            if args.heads % sp == 0:
                combos.append(("ulysses", sp))
            else:
                print(json.dumps({"impl": "ulysses", "sp": sp,
                                  "skipped": f"heads {args.heads} not "
                                             f"divisible by sp {sp}"}),
                      flush=True)
    for seq in [int(s) for s in args.seqs.split(",")]:
        for impl, sp in combos:
            set_hybrid_communicate_group(None)
            fleet.fleet.__init__()
            paddle.seed(0)
            strategy = dist.DistributedStrategy()
            strategy.hybrid_configs = {"dp_degree": 8 // sp,
                                       "sep_degree": sp}
            if impl != "dense":
                strategy.sep_impl = impl
            fleet.init(is_collective=True, strategy=strategy)
            cfg = GPTConfig(vocab_size=1024, hidden_size=args.hidden,
                            num_layers=args.layers, num_heads=args.heads,
                            max_seq_len=seq)
            model = GPTForPretraining(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         parameters=model.parameters())
            eng = fleet.distributed_engine(model, opt)
            rng = np.random.RandomState(0)
            ids = jnp.asarray(rng.randint(0, 1024, (args.batch, seq)),
                              jnp.int64)
            labels = jnp.roll(ids, -1, 1)
            jf = eng._build([ids, labels])
            comp = jf.lower(eng.params, eng.opt_state, jnp.float32(1e-4),
                            jnp.int32(1), jax.random.key(0), ids,
                            labels).compile()
            from paddle_tpu.utils.hlo_inspect import cost_analysis_dict

            ca = cost_analysis_dict(comp)
            ma = comp.memory_analysis()
            txt = comp.as_text()
            peak_mb = round((ma.temp_size_in_bytes +
                             ma.output_size_in_bytes) / 1e6, 1)
            row = {
                "impl": impl, "sp": sp, "seq": seq,
                "gflops": round(float(ca.get("flops", 0)) / 1e9, 2),
                "gbytes": round(float(ca.get("bytes accessed", 0)) / 1e9, 3),
                "peak_mb": peak_mb,
                # params+opt state live in HBM too, but temp+output dwarfs
                # them in the regime this tool exists for; the column is a
                # per-device go/no-go against the HBM budget
                "feasible": bool(peak_mb < args.hbm_gb * 1e3),
                "collective_permutes": len(
                    re.findall(r"collective-permute\(", txt)),
                "all_to_alls": len(re.findall(r"all-to-all\(", txt)),
            }
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
