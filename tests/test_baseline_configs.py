"""BASELINE.json config analogues on the 8-device virtual CPU mesh.

Config 1 (MNIST LeNet dygraph) lives in test_mnist_e2e; config 4 (GPT hybrid
dp+mp+pp) in test_pipeline + __graft_entry__.dryrun_multichip; config 5
(Wide&Deep PS) in test_ps. This file adds the engine-path coverage for:
- config 2: ResNet DataParallel over the dp axis (imgs/sec path)
- config 3: ERNIE with ZeRO sharding (fleet sharding_stage2 analogue)
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.mesh import set_hybrid_communicate_group


def _init(configs, sharding=False):
    set_hybrid_communicate_group(None)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = configs
    if sharding:
        strategy.sharding = True
    fleet.init(is_collective=True, strategy=strategy)
    return strategy


def test_resnet_dp8_engine_step():
    """BASELINE config 2 analogue: ResNet18 DataParallel, batch sharded over
    dp=8; loss decreases over steps on a fixed batch."""
    import jax

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    strategy = _init({"dp_degree": 8})
    paddle.seed(0)
    model = paddle.vision.models.resnet18(num_classes=10)
    opt = paddle.optimizer.Momentum(learning_rate=0.05,
                                    parameters=model.parameters())
    # loss_fn convention: model eats batch[:-1], loss_fn(outputs, labels)
    engine = fleet.distributed_engine(model, opt,
                                      loss_fn=paddle.nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    imgs = paddle.to_tensor(rng.randn(16, 3, 32, 32).astype(np.float32))
    labels = paddle.to_tensor(rng.randint(0, 10, (16,)).astype(np.int64))
    losses = [float(engine.step(imgs, labels).item()) for _ in range(4)]
    assert losses[-1] < losses[0], losses


def test_ernie_sharding_engine_step():
    """BASELINE config 3 analogue: ERNIE pretraining objective under ZeRO
    optimizer-state sharding (sharding axis) x dp."""
    import jax

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForPretraining

    strategy = _init({"dp_degree": 2, "sharding_degree": 4}, sharding=True)
    paddle.seed(0)
    cfg = ErnieConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                      num_heads=2, max_seq_len=64)
    model = ErnieForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=5e-4,
                                 parameters=model.parameters())
    engine = fleet.distributed_engine(model, opt)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 1024, (8, 64)).astype(np.int64)
    mlm_labels = np.where(rng.rand(8, 64) < 0.15, ids, -100).astype(np.int64)
    losses = [float(engine.step(paddle.to_tensor(ids),
                                paddle.to_tensor(mlm_labels)).item())
              for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses

    # ZeRO check: optimizer states actually sharded over the sharding axis
    sharded = [n for n, spec in engine.opt_specs.items()
               if any(e == "sharding" for e in spec)]
    assert sharded, "no optimizer state carries the sharding axis"
