"""How tests/data/scoped_probe.xplane.pb was made (PR 26), kept beside it.

On the chip: three train steps of a two-block GPT (flash kernels engaged) and
a short ServingEngine run under jax.profiler, with the program's own scopes
and spans:

    chiprun -- python3 tests/data/record_scoped_probe.py      # writes chiprun_out/

Then, anywhere, the raw trace (4.4 MB) is cut to what the reducer reads:

    python3 tests/data/record_scoped_probe.py slim chiprun_out/scoped_probe_raw.xplane.pb tests/data/scoped_probe.xplane.pb

`slim` keeps the first chip's `XLA Ops` and `XLA Modules` lines and the host's
`serve.*`, `engine.*` and `probe_window` events, every time as recorded; of an
event's metadata it keeps the name and the `tf_op` stat. An instruction's name
is its whole HLO text in the raw file; its shape and operands are cut
(`%fusion.12 = _ fusion(...)`), which leaves the opcode and the Mosaic marker
the reducer looks for, and the file under 300 KB.
"""
import glob, json, os, shutil, sys, tempfile, time

sys.path.insert(0, os.getcwd())


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(no, value):
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def slim(src, dst):
    import re

    from paddle_tpu.observability import device_trace as dt

    planes = dt.read_xplane(src)
    out = b""
    for pid, plane in enumerate(planes):
        device = plane["name"] == "/device:TPU:0"
        if not device and not plane["name"].startswith("/host:CPU"):
            continue
        body = _field(1, pid) + _field(2, plane["name"])
        used = set()
        for lid, line in enumerate(plane["lines"]):
            if device and line["name"] not in ("XLA Ops", "XLA Modules"):
                continue
            rows = [(a, b, mid) for a, b, mid in line["events"] if device
                    or plane["events"][mid]["name"] == "probe_window"
                    or plane["events"][mid]["name"].startswith(
                        dt.SPAN_PREFIXES)]
            if not rows:
                continue
            t0 = int(min(a for a, _, _ in rows))
            msg = _field(1, lid) + _field(2, line["name"]) + _field(3, t0)
            for a, b, mid in rows:
                used.add(mid)
                msg += _field(4, _field(1, mid)
                              + _field(2, int(round((a - t0) * 1e3)))
                              + _field(3, int(round((b - a) * 1e3))))
            body += _field(3, msg)
        for mid in sorted(used):
            meta = plane["events"][mid]
            name = meta["name"]
            m = dt._OPCODE_RE.match(name)
            if m:
                mark = 'custom_call_target="tpu_custom_call"'
                name = (f"{name.split(' ')[0]} = _ {m.group(1)}(...)"
                        + (f", {mark}" if mark in name else ""))
            msg = _field(1, mid) + _field(2, name)
            if meta["tf_op"]:
                msg += _field(5, _field(1, 1) + _field(5, meta["tf_op"]))
            body += _field(4, _field(1, mid) + _field(2, msg))
        body += _field(5, _field(1, 1)
                       + _field(2, _field(1, 1) + _field(2, "tf_op")))
        out += _field(1, body)
    with open(dst, "wb") as f:
        f.write(out)
    print(dst, len(out), "bytes")


if len(sys.argv) > 1 and sys.argv[1] == "slim":
    slim(sys.argv[2], sys.argv[3])
    sys.exit(0)

import jax, jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.models import GPTConfig, GPTForPretraining
from paddle_tpu.observability import device_trace, tracer
from paddle_tpu.serving import ServingEngine

print(jax.devices(), flush=True)
paddle.seed(0)
cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=2,
                max_seq_len=256)
model = GPTForPretraining(cfg)
strategy = dist.DistributedStrategy()
strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
fleet.init(is_collective=True, strategy=strategy)
opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters(),
                             weight_decay=0.01,
                             grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
eng = fleet.distributed_engine(model, opt)
rng = np.random.default_rng(0)
ids = rng.integers(0, 1000, (2, 256)).astype(np.int64)
x, y = paddle.to_tensor(ids), paddle.to_tensor(np.roll(ids, -1, 1))

d = tempfile.mkdtemp()
with paddle.amp.auto_cast(dtype="bfloat16"):
    for _ in range(2):
        float(eng.step(x, y).item())          # compile, warm
    eng.sync_to_model()
    model.eval()
    serve = ServingEngine(model, slot_count=2, ladder=(16, 32), max_new_cap=16,
                          steps_per_dispatch=4)
    for k in range(2):                        # compile both programs
        serve.submit([1, 2, 3], max_new_tokens=5, temperature=0.8, top_k=20,
                     top_p=0.9, seed=k)
    serve.run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("probe_window"):
        for _ in range(3):
            loss = eng.step(x, y)
            time.sleep(0.002)                  # an idle gap outside any span
        float(loss.item())
        for k in range(3):
            serve.submit(list(range(1, 9 + k)), max_new_tokens=9,
                         temperature=0.8, top_k=20, top_p=0.9, seed=k)
        serve.run()
    jax.profiler.stop_trace()

paths = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
print(paths, [os.path.getsize(p) for p in paths], flush=True)
os.makedirs("chiprun_out", exist_ok=True)
shutil.copy(paths[0], "chiprun_out/scoped_probe_raw.xplane.pb")

planes = device_trace.read_xplane(paths[0])
for p in planes:
    print("PLANE", p["name"], [(l["name"], len(l["events"])) for l in p["lines"]][:8])
    if p["name"].startswith("/device:TPU:0"):
        calls = [m for m in p["events"].values()
                 if "tpu_custom_call" in m["name"]]
        for m in calls[:6]:
            print("  MOSAIC", m["tf_op"], "|", m["name"][:400])
        for m in list(p["events"].values())[:40]:
            print("  OP", m["tf_op"], "|", m["name"][:110])
r = device_trace.reduce(paths[0], window="probe_window")
print(device_trace.format_table(r))
with open("chiprun_out/scoped_probe_reduced.json", "w") as f:
    json.dump(r, f, indent=1)
ev = tracer.get_tracer().events()
print("ring", len(ev), ev[-12:])
