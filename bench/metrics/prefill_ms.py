"""Fenced prefill wall per prefilled request, ms, from the scheduler's
``prefill`` phase (one step per whole-prompt prefill)."""


def read(run):
    ph = run.phases.get("prefill")
    if not ph or not ph["steps"]:
        return None
    return 1e3 * ph["wall_s"] / ph["steps"]
