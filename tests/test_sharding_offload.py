"""Sharding stage-2/3 offload + segment_size fidelity (VERDICT r1 item #5).

Reference: group_sharded_optimizer_stage2.py:48 (offload), and
group_sharded_stage3.py:80/:314 (segment_size keeps small params unsliced).
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.mesh import set_hybrid_communicate_group
from paddle_tpu.distributed.meta_parallel.sharding import (
    GroupShardedOptimizerStage2, GroupShardedStage3, group_sharded_parallel)


def _fleet(confs, sharding=False):
    set_hybrid_communicate_group(None)
    strategy = dist.DistributedStrategy()
    strategy.sharding = sharding
    strategy.hybrid_configs = confs
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def _train(offload, steps=3):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    opt2 = GroupShardedOptimizerStage2(net.parameters(), opt, offload=offload)
    rs = np.random.RandomState(0)
    for _ in range(steps):
        x = paddle.to_tensor(rs.rand(4, 8).astype(np.float32))
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt2.step()
        opt2.clear_grad()
    return net, opt


def test_eager_offload_state_is_host_resident_and_numerically_identical():
    import jax

    net_off, opt_off = _train(offload=True)
    net_on, opt_on = _train(offload=False)
    # identical numerics
    for (n1, p1), (n2, p2) in zip(net_off.named_parameters(),
                                  net_on.named_parameters()):
        np.testing.assert_allclose(p1.numpy(), p2.numpy(), rtol=1e-6,
                                   err_msg=n1)
    # offloaded states are numpy (host RAM), non-offloaded are device arrays
    for _, st in opt_off._states.values():
        assert all(isinstance(s, np.ndarray) for s in st), type(st[0])
    for _, st in opt_on._states.values():
        assert all(isinstance(s, jax.Array) for s in st), type(st[0])
    # state_dict still round-trips from host state
    sd = opt_off.state_dict()
    assert any(k.startswith("param0_state") for k in sd)


def test_engine_offload_places_opt_state_in_host_memory():
    hcg = _fleet({"dp_degree": 4, "mp_degree": 2}, sharding=True)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    opt._offload = True
    engine = fleet.distributed_engine(net, opt,
                                      loss_fn=lambda out: (out ** 2).mean())
    rs = np.random.RandomState(0)
    losses = [float(engine.step(
        paddle.to_tensor(rs.rand(8, 8).astype(np.float32))).item())
        for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    for n, st in engine.opt_state.items():
        for leaf in st:
            assert leaf.sharding.memory_kind == "pinned_host", (
                n, leaf.sharding)

    # parity vs the non-offloaded engine
    set_hybrid_communicate_group(None)
    hcg = _fleet({"dp_degree": 4, "mp_degree": 2}, sharding=True)
    paddle.seed(0)
    net2 = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt2 = paddle.optimizer.Adam(learning_rate=1e-2,
                                 parameters=net2.parameters())
    engine2 = fleet.distributed_engine(net2, opt2,
                                       loss_fn=lambda out: (out ** 2).mean())
    rs = np.random.RandomState(0)
    losses2 = [float(engine2.step(
        paddle.to_tensor(rs.rand(8, 8).astype(np.float32))).item())
        for _ in range(3)]
    np.testing.assert_allclose(losses, losses2, rtol=1e-5)
    import jax

    default_kind = jax.devices()[0].default_memory().kind
    for n, st in engine2.opt_state.items():
        for leaf in st:
            assert leaf.sharding.memory_kind in (None, default_kind)


def test_stage3_segment_size_keeps_small_params_whole():
    _fleet({"dp_degree": 1, "mp_degree": 1, "sharding_degree": 8},
           sharding=True)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 64),   # 4096 elems: sharded
                        nn.Linear(4, 4))     # 16 elems: stays whole
    GroupShardedStage3(net, segment_size=256)
    big = net[0].weight
    small = net[1].weight
    assert getattr(big, "dist_attr", None) is not None
    assert "sharding" in str(big.dist_attr)
    assert getattr(small, "dist_attr", None) is None
    # biases (64 and 4 elems) both under the 256 segment floor
    assert getattr(net[0].bias, "dist_attr", None) is None


def test_group_sharded_parallel_offload_plumbs_through():
    _fleet({"dp_degree": 1, "mp_degree": 1, "sharding_degree": 8},
           sharding=True)
    paddle.seed(0)
    net = nn.Linear(16, 16)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=net.parameters())
    model, out_opt = group_sharded_parallel(net, opt, "p_g_os", offload=True,
                                            segment_size=8)
    assert opt._offload is True and opt._zero_stage == 3
    model2, out2 = group_sharded_parallel(nn.Linear(4, 4),
                                          paddle.optimizer.SGD(
                                              learning_rate=0.1,
                                              parameters=net.parameters()),
                                          "os_g", offload=True)
    assert out2._optim._offload is True
