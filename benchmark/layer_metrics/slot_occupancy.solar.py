"""Share of decode slots in use, averaged over the window's scheduler steps (a slot in prefill is not in use); from `ServeStats.observe_cb_step`."""


def read(facts):
    c = facts["counters"]
    if not c.get("cb_steps"):
        return None
    return 100.0 * c["cb_active_slot_steps"] / (c["cb_steps"] * c["cb_slots"])
