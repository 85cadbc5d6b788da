"""points_per_s: design points completed by the whole campaigns of
the window, over the window's wall time (host clock)."""


def read(run):
    points = [j["points"] for j in run.jobs if "points" in j]
    return sum(points) / run.window_s if points else None
