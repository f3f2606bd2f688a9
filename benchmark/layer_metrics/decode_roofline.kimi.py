"""The decode program against its roofline: non-expert weights, the held experts some token of the step chose (the program's routing counter), busy slots' recurrent state read and written, live latent rows and the head slice, over 819 GB/s, over the decode program's device time."""
from benchmark.layer_metrics._kimi import decode_roofline as read  # noqa: F401
