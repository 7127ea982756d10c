"""The rules for running on the chip, rehearsed where there is none.

chip_smoke.py and bench.py are the two entry points that must own a TPU or
fail. What can be pinned on the CPU: the smoke's control flow at gpt_tiny,
the refusals, where the compile cache goes, and the small rules the bring-up
settled (device places, the peak table, the HLO reader, the launcher).
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chip_smoke / bench live at the repo root


def _run(args, env_extra, drop=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    for k in drop:
        env.pop(k, None)
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, env=env, cwd=REPO)


# ------------------------------------------------------------ chip_smoke ----

def test_smoke_phases_run_at_tiny_size():
    """The three phases, their checks included, on the virtual CPU mesh:
    interpret-mode kernel, dp over every device, serving on device 0."""
    import jax

    import chip_smoke
    from paddle_tpu.models import gpt_tiny

    k = chip_smoke.kernel_phase(1, 4, 128, 64)
    assert set(k["rel_err_vs_dense"]) == {"out", "dq", "dk", "dv"}
    assert k["path"] == "packed"

    t = chip_smoke.train_phase(gpt_tiny(), 1, 64, warmup=1, steps=2)
    assert t["dp"] == jax.device_count() and t["batch"] == t["dp"]
    assert len(t["losses"]) == 3 and t["losses"][-1] < t["losses"][0]
    assert t["flash_calls"] == 0  # no Mosaic call in a CPU program
    assert t["collectives"]["all-reduce"] >= 1  # the gradient reduction
    assert t["shards"]["batch"]["per_device"] == [1, 64]

    s = chip_smoke.serve_phase(gpt_tiny(), slot_count=4, ladder=(8, 16, 32),
                               max_new_cap=8)
    assert s["requests"] == 9 and s["rungs"] == [8, 16, 32]
    assert s["tokens"] == [4] * 7 and s["eos_tokens"] <= 3
    # bit-for-bit on the CPU, as tests/test_serving_engine.py pins
    assert s["generate_same_tokens"] == ["all", "all"]


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_entry_points_exit_nonzero_without_a_tpu(script):
    p = _run([script], {})
    assert p.returncode != 0, p.stdout
    # no result of any kind: not the smoke's ok line, not a bench metric
    for line in p.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line
    assert "no TPU" in p.stderr


# --------------------------------------------------------- compile cache ----

_CACHE_PROBE = r"""
import json
import jax
calls = []
_update = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), _update(k, v))[1]
import paddle_tpu
from paddle_tpu.core import compile_cache
print(json.dumps({"dir": compile_cache.cache_dir(),
                  "jax_dir": jax.config.jax_compilation_cache_dir,
                  "enabled": jax.config.jax_enable_compilation_cache,
                  "updates": calls}))
"""


def test_compile_cache_placed_from_outside_is_never_set_in_code(tmp_path):
    outside = str(tmp_path / "given")
    p = _run(["-c", _CACHE_PROBE], {"JAX_COMPILATION_CACHE_DIR": outside},
             drop=("FLAGS_compile_cache_dir",))
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["dir"] == outside and got["jax_dir"] == outside
    assert got["enabled"] is True
    assert "jax_compilation_cache_dir" not in got["updates"]
    # the flag cannot move it either, and "" only switches the cache off
    p = _run(["-c", _CACHE_PROBE], {"JAX_COMPILATION_CACHE_DIR": outside,
                                    "FLAGS_compile_cache_dir": ""})
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["dir"] is None and got["jax_dir"] == outside
    assert got["enabled"] is False
    assert "jax_compilation_cache_dir" not in got["updates"]


def test_compile_cache_defaults_to_the_fixed_checkout_path():
    p = _run(["-c", _CACHE_PROBE], {},
             drop=("FLAGS_compile_cache_dir", "JAX_COMPILATION_CACHE_DIR"))
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["dir"] == got["jax_dir"] == os.path.join(REPO, ".jax_cache")
    assert got["enabled"] is True


def test_suite_runs_with_the_cache_off():
    import jax

    from paddle_tpu.core import compile_cache

    assert not compile_cache.enabled()
    assert jax.config.jax_enable_compilation_cache is False


# ----------------------------------------------------------- small rules ----

def test_peak_table_is_keyed_by_device_kind_and_unknown_raises():
    from paddle_tpu.observability import peak_flops_per_sec

    assert peak_flops_per_sec("TPU v5 lite") == 197e12
    for kind in ("tpu", "cpu", "TPU v99"):
        with pytest.raises(KeyError, match="no peak"):
            peak_flops_per_sec(kind)


def test_tpu_place_without_a_tpu_is_an_error():
    import paddle_tpu as paddle

    with pytest.raises(RuntimeError, match="needs a 'tpu' device"):
        paddle.TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError, match="needs a 'tpu' device"):
        paddle.to_tensor([1.0]).cuda()
    assert paddle.CPUPlace(0).jax_device().platform == "cpu"


def test_hlo_reader_matches_the_opcode_not_the_instruction_name():
    from paddle_tpu import analysis as an

    txt = (
        "  %psum_invariant.7 = f32[1,8]{1,0} all-reduce(%param.1), "
        "channel_id=1, replica_groups={{0,1}}, to_apply=%region_0.0\n"
        "  %ag = ((f32[4]{0:T(256)}), f32[16]{0}) all-gather-start(%p), "
        "channel_id=2, dimensions={0}\n"
        "  %agd = f32[16]{0} all-gather-done(%ag)\n"
        "  %all-reduce.9 = f32[4]{0} add(%agd, %agd)\n"
        "  ROOT %cc = (bf16[96,1024,64]{2,1,0}, f32[96,1024,128]{2,1,0}) "
        "custom-call(%q.1, %agd), custom_call_target=\"tpu_custom_call\"\n"
        "  %q.1 = bf16[96,1024,64]{2,1,0:T(8,128)(2,1)} fusion(%p), "
        "kind=kLoop, calls=%fused\n")
    p = an.Program("t", hlo_text=txt)
    assert p.count_ops("all-reduce") == 1   # not the add named all-reduce.9
    assert p.count_ops("all-gather") == 1   # -start counts, -done does not
    (call, operands), = p.custom_calls("tpu_custom_call")
    assert call.name == "%cc"
    assert operands == ["bf16[96,1024,64]{2,1,0:T(8,128)(2,1)}", "f32[16]{0}"]


def test_combining_probe_reads_what_the_reader_counts():
    """The backend probe and the contracts share one reader, so a backend
    is called combining exactly when its two-psum program shows one
    all-reduce to the opcode parser."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu import analysis as an

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    fm = jax.shard_map(
        lambda a, b: (jax.lax.psum(a, "dp"), jax.lax.psum(b, "dp")),
        mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=(P(), P()))
    z = np.zeros((jax.device_count(), 4), np.float32)
    n = an.Program("probe", compiled=jax.jit(fm).lower(z, z).compile()) \
        .count_ops("all-reduce")
    assert n in (1, 2)
    assert an.backend_combines_collectives() == (n == 1)


@pytest.mark.parametrize("conf", [{"dp_degree": 8},
                                  {"dp_degree": 4, "mp_degree": 2},
                                  {"dp_degree": 4, "sep_degree": 2}])
def test_flash_step_lowers_for_the_tpu_under_a_mesh(monkeypatch, conf):
    """GSPMD cannot partition a Mosaic kernel: jax refuses to lower one in a
    multi-device program ("Please wrap the call in a shard_map"), which is
    how the first dp4 step on four chips died. The engine scopes its mesh
    and the kernel entry wraps itself; this lowers the real step for the TPU
    target from here, where that refusal is raised."""
    import importlib

    import jax
    import numpy as np
    from jax import export as jexport

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTForPretraining, gpt_tiny

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    paddle.set_flags({"use_flash_attention": True,
                      "pallas_interpret_ok": True})
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = conf
    fleet.init(is_collective=True, strategy=strategy)
    model = GPTForPretraining(gpt_tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    eng = fleet.distributed_engine(model, opt)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, 1024, (8, 128)).astype(np.int64))
    with paddle.amp.auto_cast(dtype="bfloat16"):
        mod = jexport.export(eng._build([ids, ids]), platforms=["tpu"])(
            eng.params, eng.opt_state, jnp.float32(1e-4), jnp.int32(1),
            jax.random.key(0), ids, ids).mlir_module()
    assert "tpu_custom_call" in mod


def test_stashed_step_relowers_under_the_autocast_it_was_traced_in():
    """Autocast is trace-time state. The first chip run read f32 operands on
    the flash calls of a bf16 step because the analysis re-lowered the
    stashed step outside the user's auto_cast scope — a program that never
    ran. The stash now re-enters the scope it was taken in."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.dispatch import amp_ctx
    from paddle_tpu.distributed.engine import TrainStepEngine

    net = paddle.nn.Sequential(paddle.nn.Linear(8, 8), paddle.nn.ReLU(),
                               paddle.nn.Linear(8, 8))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=net.parameters())
    eng = TrainStepEngine(net, opt, loss_fn=paddle.nn.MSELoss())
    x = paddle.to_tensor(np.ones((8, 8), np.float32))
    with paddle.amp.auto_cast(dtype="bfloat16"):
        eng.step(x, x)
    assert amp_ctx() is None
    (fn, avals), = eng._exec_stash.values()
    assert "bf16" in fn.lower(*avals).as_text()
    assert amp_ctx() is None  # and the scope does not leak out of lower()


def test_launcher_refuses_two_workers_on_a_tpu_host(monkeypatch):
    import importlib

    from paddle_tpu.distributed.spawn import spawn
    launch = importlib.import_module("paddle_tpu.distributed.launch.main")

    # the suite pins JAX_PLATFORMS=cpu: a CPU-platform job, any worker count
    launch.one_controller_per_host(4, "test")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(launch.glob, "glob", lambda pat: (
        ["/dev/vfio/0", "/dev/vfio/1"] if "vfio" in pat else []))
    assert launch.tpu_chips_on_host() == 2
    launch.one_controller_per_host(1, "test")
    with pytest.raises(SystemExit, match="One controller process"):
        launch.one_controller_per_host(2, "test")
    with pytest.raises(SystemExit, match="One controller process"):
        spawn(print, nprocs=2)


@pytest.mark.slow
def test_dryrun_multichip_forces_virtual_cpu_mesh():
    """A fresh interpreter with no platform chosen: dryrun_multichip pins
    itself to the virtual CPU mesh before the backend starts."""
    p = _run(["-c", "import __graft_entry__ as g\n"
                    "g.dryrun_multichip(4)\nprint('DRYRUN_DONE')\n"],
             {}, drop=("JAX_PLATFORMS", "XLA_FLAGS"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "DRYRUN_DONE" in p.stdout
