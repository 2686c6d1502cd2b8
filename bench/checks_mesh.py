"""``bench/checks.py`` for training cells over a mesh: the same readings,
with the fault only a mesh can have (``bench/variants_mesh.py``) among the
variants, and a control that spreads the reference over the cell's chips.
The benchmark's own runs never run this.

    python bench/checks_mesh.py --workload train.28L.mesh2x2 --variant program --seeds 1,2
    python bench/checks_mesh.py --workload train.28L.mesh2x2 --variant control --seeds 1,2
    python bench/checks_mesh.py --workload train.28L.mesh2x2 --variant no_exchange --seeds 1

The control is the spread float32 reference against itself with float8
(e4m3) matmul operands, one step below the configuration's bf16.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import checks, harness, variants, variants_mesh  # noqa: E402
from bench.drivers import train  # noqa: E402


def control(cell: harness.Cell, devices) -> list[tuple[str, float, float]]:
    import jax
    import jax.numpy as jnp

    from bench import generate
    from bench.drivers import train_mesh
    from bench.reference import qwen2_spread

    cfg, t = cell.config, cell.traffic
    opt = cfg["training"]["optimizer"]
    key = jax.random.PRNGKey(harness.seed32(cell.seed))
    batches = [generate.lm_batch(cell.seed, i, t["batch"], t["seq"],
                                 cfg["vocab_size"]) for i in range(train.WARM)]
    want = qwen2_spread.train3(cfg, opt, key, batches, devices)
    low = qwen2_spread.train3(cfg, opt, key, batches, devices,
                              operand_dtype=jnp.float8_e4m3fn)
    got, _ = train_mesh.compare_run(low["losses"], low["grad"], low["change"], want)
    return got


base_reading = checks.reading


def reading(cell: harness.Cell, variant: str, devices) -> dict:
    if variant == "control":
        return {"checks": control(cell, devices)}
    return base_reading(cell, variant, devices)


def main(argv=None) -> int:
    with train.patched(checks, reading=reading), \
            train.patched(variants, PATCHES={**variants.PATCHES,
                                             **variants_mesh.PATCHES}):
        return checks.main(argv)


if __name__ == "__main__":
    sys.exit(main())
