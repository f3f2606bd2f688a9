"""The decode program against its roofline: non-expert weights and the head (the tied embedding, once), the experts some token of the step chose (the program's routing counter), live K and V rows, busy slots' tails read and written, over 819 GB/s, over the decode program's device time."""
from benchmark.layer_metrics._zaya import decode_roofline as read  # noqa: F401
