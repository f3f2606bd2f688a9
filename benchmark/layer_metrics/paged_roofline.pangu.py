"""The paged decode kernel against its roofline at 128 heads x 2 query rows over one shared latent row: the 6 calls of a step need the longer of their operations (2 x 128 x 2 x 1,088 a live token) over 197 TFLOP/s and their 576-value rows over 819 GB/s; over the device time of the trace's `singa_paged_decode` rows."""
from benchmark.layer_metrics._pangu import paged_roofline as read  # noqa: F401
