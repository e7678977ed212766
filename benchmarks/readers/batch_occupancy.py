"""Mean over the window's steps of lanes in use over lanes, sampled by the
driver at each ``step()`` call."""
import stats


def read(raw, params, env):
    if not raw.get("occupancy_samples"):
        return None
    return 100.0 * stats.occupancy(raw["occupancy_samples"], raw["max_batch"])
