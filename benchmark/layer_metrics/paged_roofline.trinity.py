"""The paged decode kernel against its roofline: the K and V rows a step's 16 `singa_paged_decode` calls need (4 over every live token, 12 over the window's) over 819 GB/s, over the device time of the trace's `singa_paged_decode` rows."""
from benchmark.layer_metrics._trinity import paged_roofline as read  # noqa: F401
