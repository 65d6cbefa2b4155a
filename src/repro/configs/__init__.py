"""Architecture registry: ``--arch <id>`` resolves through here."""
from __future__ import annotations

from repro.configs.base import (SHAPES, SIZES, ArchSpec, ShapeSpec,
                                cache_specs, input_specs, param_specs)
from repro.configs import (codeqwen15_7b, command_r_plus_104b, granite_moe_3b,
                           paligemma_3b, qwen15_4b, qwen3_moe_30b,
                           recurrentgemma_2b, resnet18_cifar10, rwkv6_3b,
                           starcoder2_3b, whisper_small)

ARCHS = {
    spec.name: spec
    for spec in (
        recurrentgemma_2b.SPEC,
        qwen15_4b.SPEC,
        command_r_plus_104b.SPEC,
        starcoder2_3b.SPEC,
        codeqwen15_7b.SPEC,
        granite_moe_3b.SPEC,
        qwen3_moe_30b.SPEC,
        paligemma_3b.SPEC,
        rwkv6_3b.SPEC,
        whisper_small.SPEC,
    )
}


def get_arch(name: str) -> ArchSpec:
    if name not in ARCHS:
        raise KeyError(
            f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def all_cells():
    """Every assigned (arch, shape) cell with its skip status."""
    for arch in ARCHS.values():
        for shape in SHAPES.values():
            yield arch, shape, arch.skip_reason(shape.name)
