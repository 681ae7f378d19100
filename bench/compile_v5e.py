"""Compile a cell's production sweep for a described TPU v5e, no chip needed,
and compare two such compiles with their metadata stripped.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py compile --workload nell2-r16 \
        --out new.hlo [--program <checkout>]
    python3 bench/compile_v5e.py compare old.hlo new.hlo

``compile`` builds the cell's tensor from a fixed seed (shapes follow from
the configuration alone on one chip), runs the program's FLYCOO build and
runtime preparation on the host, and compiles ``make_als_sweep(rt, mesh,
backend=<job's>)`` ahead of time for a described ``v5e:2x2`` (the cell's
chips of it), with the Pallas kernels compiled (not interpreted). It writes
``compiled.as_text()``. ``--program`` takes the program from another
checkout's ``src/`` (a parent commit, say) with this checkout's cells.

``compare`` checks that two texts hold the same instructions once what
only names or locates them is taken away: every ``metadata={...}`` (the
``op_name`` scopes and source lines), and the debug locations inside each
Pallas kernel's serialized Mosaic module. It exits 0 when they match.
"""
from __future__ import annotations

import argparse
import base64
import os
import re
import sys

import phases

BENCH = os.path.dirname(os.path.abspath(__file__))
_METADATA = re.compile(r",? metadata=\{[^{}]*\}")
_KERNEL_BODY = re.compile(r'"body":"([^"]*)"')


def compile_sweep(workload: str, program: str, seed: int = 1) -> str:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [BENCH, os.path.join(program, "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import harness
    from repro.core import distributed as dist
    from repro.core.cpals import make_als_sweep
    from repro.core.flycoo import build_flycoo
    from repro.core.tensors import SparseTensor
    from repro.runtime import execution

    # Trace the kernels as they run on the chip (compiled, block size
    # 1024): the host has none, so tell the policy it has.
    execution.capability = lambda: execution.Capability("tpu", True, "")
    execution.set_execution_mode("compiled")
    cell = harness.load_cell(workload)
    inp = harness.make_inputs(cell, seed)
    ft = build_flycoo(SparseTensor(inp.coords, inp.values, inp.shape),
                      num_workers=cell.chips)
    rt, _ = dist.prepare_runtime(ft, int(cell.traffic["rank"]),
                                 gather_dtype=cell.traffic["gather_dtype"])
    del ft, inp
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:cell.chips]), (dist.AXIS,))
    sharded = NamedSharding(mesh, P(dist.AXIS))
    replicated = NamedSharding(mesh, P())
    d, r = cell.chips, rt.rank

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = [sds((d, rt.nnz_cap, rt.nmodes), jnp.int32, sharded),
            sds((d, rt.nnz_cap), jnp.float32, sharded),
            sds((d, rt.nnz_cap), jnp.bool_, sharded),
            sds((d,), jnp.float32, sharded)]
    args += [sds((p, r), jnp.float32, replicated) for p in rt.i_pad]
    args += [sds((r,), jnp.float32, replicated),
             sds((), jnp.bool_, replicated)]
    sweep = make_als_sweep(rt, mesh, backend=cell.traffic["backend"])
    return sweep.lower(*args).compile().as_text()


def _kernel_asm(body: str) -> str:
    """A serialized Mosaic module, printed without its debug locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def instructions(hlo_text: str) -> list[str]:
    """Every instruction line, with metadata and kernel locations taken
    away."""
    out = []
    for line in hlo_text.splitlines():
        if not phases.HLO_INSTRUCTION.match(line):
            continue
        line = _METADATA.sub("", line)
        m = _KERNEL_BODY.search(line)
        if m:
            line = (line[:m.start(1)] + _kernel_asm(m.group(1))
                    + line[m.end(1):])
        out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("compile")
    c.add_argument("--workload", required=True)
    c.add_argument("--out", required=True)
    c.add_argument("--program", default=os.path.dirname(BENCH))
    k = sub.add_parser("compare")
    k.add_argument("old")
    k.add_argument("new")
    args = ap.parse_args(argv)
    if args.cmd == "compile":
        with open(args.out, "w") as f:
            f.write(compile_sweep(args.workload, args.program))
        return 0
    old, new = (instructions(open(p).read()) for p in (args.old, args.new))
    diff = sum(a != b for a, b in zip(old, new)) + abs(len(old) - len(new))
    print(f"{len(old)} and {len(new)} instructions; "
          f"{diff} differ once metadata is stripped")
    return 0 if diff == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
