"""The decode program against its roofline: non-expert weights, the held experts some token of the step chose, busy slots' recurrent state read and written, live K/V rows and the head slice, over 819 GB/s, over the decode program's device time."""
from benchmark.layer_metrics._solar import decode_roofline as read  # noqa: F401
