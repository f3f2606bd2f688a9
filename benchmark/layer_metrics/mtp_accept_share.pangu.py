"""Drafts accepted over drafts verified in the window (`cb_drafts_accepted` / `cb_drafts_made`), percent: what the lossless accept-or-resample rule gives on this seed's weights at the cell's temperature; reported as measured."""
from benchmark.layer_metrics._pangu import mtp_accept_share as read  # noqa: F401
