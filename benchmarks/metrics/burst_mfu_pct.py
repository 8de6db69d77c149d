"""The learn burst's share of the chip's peak by the device's own clock:
the learner's model FLOPs (``model_flops`` of the configuration's
reference: ``grad_step``, once per gradient step of the burst) over the
device seconds of the burst's loop in the traced slice
(``_common.learn_burst_seconds``), over the peaks table's bf16 peak.
float32 contractions at ``highest`` run six bfloat16 passes each, so a
sixth of the peak is this number's ceiling there."""
from benchmarks.metrics._common import learn_burst_seconds


def read(record):
    seconds = learn_burst_seconds(record)
    if not seconds:
        return None
    done = record["flops"]["grad_step"] * record["episode_steps"]
    return 100.0 * done / seconds / record["peaks"]["bf16_flops"]
