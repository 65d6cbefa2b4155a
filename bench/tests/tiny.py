"""A tiny configuration and traffic for driving a whole run on the CPU:
the cell's shapes of code, at widths a test can hold."""
import copy
import json
import os

from harness import spec

CONFIG = {"model_type": "qwen2", "hidden_act": "silu", "hidden_size": 64,
          "intermediate_size": 128, "num_attention_heads": 4,
          "num_key_value_heads": 4, "num_hidden_layers": 4,
          "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 1e6,
          "tie_word_embeddings": False, "attention_bias": True,
          "torch_dtype": "bfloat16"}


def cell(workload: str, **traffic) -> spec.Cell:
    """The committed cell ``workload`` with the tiny configuration and its
    traffic file's values overridden by ``traffic``."""
    real = spec.load_cell(workload)
    tr = copy.deepcopy(real.traffic)
    tr.update(traffic)
    return spec.Cell(name=workload, chips=real.chips, config_name="tiny",
                     config=dict(CONFIG), traffic=tr, limits=real.limits,
                     end_to_end=real.end_to_end, per_layer=real.per_layer)


TRAIN = dict(batch=4, seq=32, microbatches=2)
PIPE = dict(batch=4, seq=32, microbatches=4)
SERVE = dict(slots=4, cache_len=96, prefill_chunk_tokens=16, rate_per_s=4.0,
             prompt_lens=[8, 16, 32], prompt_weights=[0.5, 0.3, 0.2],
             output_lens=[4, 8, 64], output_weights=[0.5, 0.3, 0.2],
             check_requests=4)


def fixture(name: str) -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", name)


def load_fixture(name: str):
    with open(fixture(name)) as f:
        return json.load(f)
