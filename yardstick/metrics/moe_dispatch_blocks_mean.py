"""Layer math and kernels. Blocks of rows the held experts' dispatch
loop ran in the last step of the window, averaged over the expert layers
(the program's gauge ``dl4j_moe_dispatch_blocks``, published from an
in-step counter when a ``fit()`` call ends): the held assignments over
the block the layer's shapes give, rounded up. The experts' gather,
grouped products and scatter-add cost by it; 1 is a load that fits one
block. A program without the gauge gives None."""


def read(obs):
    from deeplearning4j_tpu.observe.registry import default_registry
    blocks = default_registry().get_metric("dl4j_moe_dispatch_blocks")
    if blocks is None:
        return None
    values = list(blocks.series().values())
    return sum(values) / len(values) if values else None
