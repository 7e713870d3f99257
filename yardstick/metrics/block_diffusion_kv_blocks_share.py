"""Layer math and kernels. Of the (query block, key block) tiles of one
head's block-diffusion attention, the share the flash kernels' grid
computes, in % (the program's gauges ``dl4j_flash_kv_blocks_visited`` over
``dl4j_flash_kv_blocks_total`` under the scope ``attn.block_diffusion``,
set from the kernel's own grid when the step is traced). The visible pairs
are 25.0% at 8,192 tokens a row; 1024 x 1024 tiles visit 80 of 256, 31.3%;
100 is a kernel that skips nothing. A program without the gauges, or
without that scope among them, gives None."""

SCOPE = "attn.block_diffusion"


def _under_scope(registry, name):
    metric = registry.get_metric(name)
    if metric is None:
        return None
    values = [v for labels, v in metric.series().items()
              if SCOPE in str(labels)]
    return values[-1] if values else None


def read(obs):
    from deeplearning4j_tpu.observe.registry import default_registry
    registry = default_registry()
    visited = _under_scope(registry, "dl4j_flash_kv_blocks_visited")
    total = _under_scope(registry, "dl4j_flash_kv_blocks_total")
    if visited is None or not total:
        return None
    return 100.0 * visited / total
