"""Gradient-compression collective benchmark (beyond-paper §Perf item).

Lowers the same data-parallel train gradient twice on an 8-device host
mesh — plain psum vs int8-compressed psum — and parses the collective
bytes out of both compiled modules.  The byte ratio is mesh-size-invariant
(payload / 4 with f32 grads), which is what transfers to the 256-chip pod.

Runs in a subprocess so the 8-device XLA flag doesn't leak into the
benchmark process.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import List, Tuple

Row = Tuple[str, float, str]

_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json, sys
sys.path.insert(0, "src")
from repro.configs.base import ArchConfig
from repro.models import init_params
from repro.training.step import make_loss_fn
from repro.distributed.compression import make_compressed_grad_fn
from repro.launch.dryrun import collective_bytes
from repro.compat import make_mesh, shard_map
from jax.sharding import PartitionSpec as P

cfg = ArchConfig(name="b", family="dense", num_layers=2, d_model=256,
                 num_heads=4, kv_heads=2, d_ff=512, vocab=1024, head_dim=64,
                 attn_chunk=64, tie_embeddings=True)
params = init_params(cfg, jax.random.PRNGKey(0))
loss_fn = make_loss_fn(cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (16, 64), 0, 1024)
batch = {"tokens": toks, "labels": toks}
mesh = make_mesh((8,), ("data",))

def plain(params, batch):
    def local(p, b):
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        l = jax.lax.pmean(l, "data")
        g = jax.tree.map(lambda x: jax.lax.pmean(x, "data"), g)
        return l, g
    return shard_map(local, mesh=mesh, in_specs=(P(), P("data")),
                     out_specs=(P(), P()))(params, batch)

comp = make_compressed_grad_fn(loss_fn, mesh)
c_plain = jax.jit(plain).lower(params, batch).compile()
c_comp = comp.lower(params, batch).compile()
b_plain = collective_bytes(c_plain.as_text())
b_comp = collective_bytes(c_comp.as_text())
print(json.dumps({"plain": b_plain, "comp": b_comp}))
"""


def run() -> List[Row]:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_BODY)],
        capture_output=True, text=True, timeout=540,
        # the child only counts bytes on a virtual CPU mesh: pin it to
        # the CPU so it never contends for a chip its parent holds
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    if out.returncode != 0:
        raise RuntimeError(f"collectives child exited {out.returncode}: "
                           f"{out.stderr[-400:]}")
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    plain_b = rec["plain"]["wire_bytes"]
    comp_b = rec["comp"]["wire_bytes"]
    return [
        ("collectives/plain_psum_wire_bytes", plain_b, ""),
        ("collectives/int8_psum_wire_bytes", comp_b,
         f"reduction={plain_b / max(comp_b, 1):.2f}x"),
    ]
