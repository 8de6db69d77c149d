"""The whole training step's share of the chip's peak: model FLOPs from
shapes (``model_flops`` of the configuration's reference module: the policy
forward per environment step, the learner's forward and backward per
gradient step; the run record's ``flops``) times the rates this
run measured, over the peaks table's bf16 peak."""
from benchmarks.metrics._common import env_steps, window_seconds


def read(record):
    span = window_seconds(record)
    if not span:
        return None
    fl = record["flops"]
    grad_steps = record["episode_steps"] * record["window_episodes"]
    done = env_steps(record) * fl["env_step"] + grad_steps * fl["grad_step"]
    return 100.0 * done / span / record["peaks"]["bf16_flops"]
