"""Layer math and kernels. Imbalance of the routing in the last step of
the window: the largest number of assignments one held expert took over
the mean a held expert took, averaged over the expert layers (the
program's gauges ``dl4j_moe_expert_load_max`` and
``dl4j_moe_expert_load_mean``, published from in-step counters when a
``fit()`` call ends). 1 is an even load. The grouped products' row counts
follow it."""


def read(obs):
    from deeplearning4j_tpu.observe.registry import default_registry
    reg = default_registry()
    largest = reg.get_metric("dl4j_moe_expert_load_max")
    mean = reg.get_metric("dl4j_moe_expert_load_mean")
    if largest is None or mean is None:
        return None
    means = mean.series()
    ratios = [v / means[k] for k, v in largest.series().items()
              if means.get(k)]
    return sum(ratios) / len(ratios) if ratios else None
