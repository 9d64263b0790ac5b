import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


class PeelLevels(list):
    """``(aug_cap, bound)`` per recorded ``_peel_step`` call."""

    def caps(self):
        return [cap for cap, _ in self]

    def want(self, ceiling):
        """Per level: min(ceiling, max(64, least power of two >= bound))."""
        out = []
        for _, bound in self:
            p = 1
            while p < bound:
                p *= 2
            out.append(min(ceiling, max(64, p)))
        return out


@pytest.fixture
def peel_levels(monkeypatch):
    """Records every ``_peel_step`` call of the device builds run under
    it: per level, the augmentation rows the builder gave it and, counted
    edge by edge from the edge list it peels, its eligible-degree bound
    (the out-degree sum over active vertices of degree <= d_cap; degree
    = in + out when directed)."""
    import inspect

    from repro.core import hierarchy
    step = hierarchy._peel_step
    sig = inspect.signature(step)
    levels = PeelLevels()

    def recording(*args, **kwargs):
        call = sig.bind(*args, **kwargs)
        call.apply_defaults()
        a = call.arguments
        n, d_cap = a["n"], a["d_cap"]
        active = np.asarray(a["active"])
        out_deg, deg = [0] * n, [0] * n
        for s, d in zip(np.asarray(a["src"]).tolist(),
                        np.asarray(a["dst"]).tolist()):
            if s < n:
                out_deg[s] += 1
                deg[s] += 1
                if a["directed"]:
                    deg[d] += 1
        levels.append((a["aug_cap"],
                       sum(out_deg[v] for v in range(n)
                           if active[v] and deg[v] <= d_cap)))
        return step(*args, **kwargs)

    monkeypatch.setattr(hierarchy, "_peel_step", recording)
    return levels
