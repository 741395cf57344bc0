"""Mean tokens routed to one held expert in one MoE layer per decode step,
over the window: the engine's routed-work counter (``serve.routed``), its
token -> held-expert assignments over (MoE layers x held experts) summed
over the window's decode steps (experts)."""
from bench import phases


def read(run):
    w = phases.window_counters(run)
    name = getattr(phases.names(), "ROUTED", None)
    c = (w or {}).get(name) or {}
    if not c.get("layer_experts"):
        return None
    return c["assignments"] / c["layer_experts"]
