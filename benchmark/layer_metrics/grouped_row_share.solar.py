"""Expert products the chunks' grouped form made (`cb_grouped_rows`: assignments of real rows on held experts) over those the dense walk would have (`cb_grouped_row_slots`: rows x held experts), percent: 2.5 under a balanced router (8 / 320)."""
from benchmark.layer_metrics._solar import grouped_row_share as read  # noqa: F401
