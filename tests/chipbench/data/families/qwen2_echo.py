"""A second family for the benchmark's tests: the Qwen2 family's code,
found under a name of its own in the test data, saying so as it makes the
weights.  A later configuration of a new architecture brings a file like
this one beside its config, cell and metrics, and edits none."""

import importlib.util
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_QWEN2 = os.path.join(_HERE, "..", "..", "..", "..", "chipbench", "families",
                      "qwen2.py")
_spec = importlib.util.spec_from_file_location("_qwen2_for_echo", _QWEN2)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

engine, widest_gap = _base.engine, _base.widest_gap
lm_config = _base.lm_config
layer_matmul_params = _base.layer_matmul_params
kv_bytes_per_token = _base.kv_bytes_per_token


def make_weights(cfg, seed, mesh=None):
    print(f"[family] qwen2_echo makes the weights of {cfg['name']}",
          file=sys.stderr)
    return _base.make_weights(cfg, seed, mesh)
