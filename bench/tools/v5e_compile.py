"""Compile a cell's chunk-prefill program, and its decode and migration programs
(a rollout mix) or its lane read-back (an admission mix), for one chip of
a described v5e:2x2 topology, on a host with no chip, and print each program's
``memory_analysis()``.  Nothing runs: this says whether the programs compile and
fit, not how fast they are.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m bench.tools.v5e_compile <workload>
"""

from __future__ import annotations

import importlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from bench import run as R
    from bench.traffic import generator as G
    from repro.engine import worker as W
    from repro.engine.sampler import SamplerConfig
    from repro.models import model as M

    cell = R.load_cell(argv[0])
    fam = importlib.import_module(f"bench.configs.{cell.family}")
    cfg = fam.program_config(cell.config_name, cell.spec)
    tr = cell.traffic
    admit = tr.get("serve") == "admit"
    cap = -(-tr["max_prompt"] // 16) * 16 if admit else G.capacity_bound(tr)
    slots = tr["n_prompts"] * tr["group_size"]
    ps = 16
    pages = cap // ps
    blocks = slots * pages + 1
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"      # take the program's TPU branch (Pallas)

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                           sharding=chip), tree)

    params = sds(jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    pool = sds(jax.eval_shape(lambda: M.init_paged_pool(cfg, None, slots, blocks,
                                                        ps, pages)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    progs = {"chunk prefill (1 x 32)": W._paged_chunk.lower(
        cfg, params, pool, i32(), i32(1, 32), i32(), mesh=None)}
    if admit:
        from bench.admit import lane_kv

        progs["lane read-back"] = lane_kv.lower(pool, i32(), next(iter(pool["blocks"])))
    else:
        sampler = SamplerConfig(temperature=tr["temperature"], top_p=tr["top_p"])
        progs[f"decode ({tr['quantum']} steps x {slots} lanes)"] = W._decode_loop.lower(
            cfg, params, pool, i32(slots), jax.ShapeDtypeStruct((slots,), bool,
                                                                sharding=chip),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32, sharding=chip),
            tr["quantum"], None, sampler, mesh=None)
        progs[f"migration gather ({pages} pages)"] = W._gather_pages.lower(
            pool, i32(pages))
        pages_sds = jax.eval_shape(W._gather_pages, pool, i32(pages))
        state = sds(jax.eval_shape(lambda p: M.paged_gather_state(p, 0), pool))
        progs[f"migration ingest ({pages} pages)"] = W._paged_ingest.lower(
            pool, sds(pages_sds), i32(pages), state, i32(), i32(pages))
    out = {}
    for name, lowered in progs.items():
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        row = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes")}
        row["tpu_custom_call"] = compiled.as_text().count("tpu_custom_call")
        out[name] = row
        print(f"{cell.name} {name}: {row}", flush=True)
    print(json.dumps({cell.name: out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
