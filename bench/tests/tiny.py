"""Each cell of ``BENCHMARK.json`` cut to a size a CPU test can run: the
same driver, loop and checks, with a small problem. Only sizes change,
and with d the decay rate, so that the cut keeps the cell's condition:
σ_min² below ν², cond(H) ≈ 1/ν² = 1e4."""

import spec

CUTS = {
    "lib-expdecay-16k": (
        {"problem": {"n": 2048, "d": 64, "spectrum_rate": 0.9, "nu": 0.01},
         "solver_m_max": 64},
        {}),
}


def cell(name: str):
    """(workload, config, mix) of the named cell at its tiny size."""
    bm = spec.load_benchmark()
    wl = spec.workload(bm, name)
    cfg = spec.config(bm, wl["config"])
    mix = spec.traffic(wl["traffic"])
    cfg_cut, mix_cut = CUTS[name]
    cfg_cut = dict(cfg_cut)
    m_max = cfg_cut.pop("solver_m_max", None)
    cfg.update(cfg_cut)
    if m_max is not None:
        cfg["solver"] = {**cfg["solver"], "m_max": m_max}
    mix.update(mix_cut)
    return wl, cfg, mix
