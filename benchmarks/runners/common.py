"""What both runners need from the program: the model from a configuration
file, and counter deltas."""
from __future__ import annotations

from typing import Dict, Iterable


def gpt_config(config: dict):
    """A benchmarks/configs/*.json (huggingface GPT-2 keys) -> GPTConfig.
    The only departure from the source is the padded vocabulary."""
    from paddle_tpu.models import GPTConfig

    if config.get("n_inner") not in (None, 4 * config["n_embd"]):
        raise ValueError("GPTConfig's MLP is 4x the hidden size")
    return GPTConfig(vocab_size=int(config["padded_vocab_size"]),
                     hidden_size=int(config["n_embd"]),
                     num_layers=int(config["n_layer"]),
                     num_heads=int(config["n_head"]),
                     max_seq_len=int(config["n_positions"]))


def build_model(config: dict, seed: int):
    """GPTForPretraining with weights made on the device from the seed (the
    program's own initializers, one jax.random call per parameter)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group
    from paddle_tpu.models import GPTForPretraining

    set_hybrid_communicate_group(None)
    paddle.seed(int(seed))
    return GPTForPretraining(gpt_config(config))


def state_arrays(model) -> dict:
    return {k: v._data for k, v in model.state_dict(
        include_non_persistable_buffer=True).items()}


class Counters:
    """Deltas of core.monitor counters since the last mark()."""

    def __init__(self, names: Iterable[str]):
        self.names = tuple(names)
        self._base: Dict[str, int] = {}
        self.mark()

    def _read(self) -> Dict[str, int]:
        from paddle_tpu.core import monitor

        return {n: monitor.stat(n).get() for n in self.names}

    def mark(self) -> None:
        self._base = self._read()

    def delta(self) -> Dict[str, int]:
        now = self._read()
        return {n: now[n] - self._base[n] for n in self.names}
