"""JAX model zoo: dense GQA, MoE, MLA, SSM, xLSTM, hybrid, enc-dec, VLM."""
from . import attention, blocks, layers, model, moe, ssm, xlstm  # noqa: F401
from .model import (cache_init, count_params, decode_step, forward,  # noqa: F401
                    init_params, loss_fn, prefill, stages_for)
