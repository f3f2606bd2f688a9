"""The multi-token prediction module's share of a verify-and-draft step's device time: in every run of the decode program in the kept trace, from the start of the op that reads W_eh (15,360 x 7,680, the module's entry) to the run's end, over the run."""
from benchmark.layer_metrics._pangu import mtp_step_share as read  # noqa: F401
