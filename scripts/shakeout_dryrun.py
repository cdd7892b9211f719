"""Dev shakeout: dry-run machinery on 8 host devices, reduced configs."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

from repro.common.config import ShapeSpec
from repro.configs import ARCHS, reduced
from repro.launch import dryrun
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))

SHAPES = [
    ShapeSpec("train_4k", 64, 4, "train"),       # tiny stand-ins
    ShapeSpec("prefill_32k", 128, 4, "prefill"),
    ShapeSpec("decode_32k", 128, 8, "decode"),
]

fails = []
for arch, cfg in ARCHS.items():
    rcfg = reduced(cfg).replace(dtype="bfloat16")
    for shape in SHAPES:
        try:
            dryrun.run_cell(arch, shape.name, "local",
                            out_dir="/tmp/shakeout", cfg=rcfg,
                            mesh=mesh, shape=shape)
        except Exception as e:
            import traceback; traceback.print_exc()
            fails.append((arch, shape.name, str(e)[:120]))
print("FAILS:", fails if fails else "none")
