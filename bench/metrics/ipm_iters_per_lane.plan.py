"""Interior-point iterations per lane in the window (EngineStats: cold
plus warm iterations over lanes solved)."""


def read(run):
    c = run["layer"]
    if not c.get("lanes"):
        return None
    return (c["cold_iterations"] + c["warm_iterations"]) / c["lanes"]
