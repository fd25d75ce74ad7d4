"""Worker process for the multi-process mesh prototype test.

Run as: python -m tests.multiproc_q1_worker <process_id> <num_processes>
        <coordinator_port> <rows_per_process>

Each process owns 4 virtual CPU devices; jax.distributed stitches them
into one global backend (the one-PJRT-client-per-executor-JVM model).
The q1 distributed step runs UNCHANGED over the global mesh — its
hash_shuffle all_to_all crosses process boundaries through the
distributed CPU backend. Every process verifies the globally-gathered
result against the host numpy oracle and prints Q1_MULTIPROC_MATCH.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main() -> None:
    pid, n_procs, port, rows_per_proc = (int(a) for a in sys.argv[1:5])
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=n_procs,
        process_id=pid,
    )
    assert jax.process_count() == n_procs
    n_global_devices = jax.device_count()

    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_table,
        q1_distributed_step,
        tpch_q1_numpy,
    )
    from spark_rapids_jni_tpu.parallel.distributed import (
        shard_table_multiprocess,
    )
    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS

    # deterministic global dataset: every process generates the WHOLE
    # table from the same seed and contributes its own row slice
    n = rows_per_proc * n_procs
    full = lineitem_table(n, seed=11)
    lo, hi = pid * rows_per_proc, (pid + 1) * rows_per_proc
    local = Table([
        Column(c.dtype, c.data[lo:hi],
               None if c.validity is None else c.validity[lo:hi])
        for c in full.columns
    ])

    mesh = jax.sharding.Mesh(np.array(jax.devices()), (EXEC_AXIS,))
    sharded = shard_table_multiprocess(local, mesh)

    # every executor returns the whole sorted answer (the plan's collect
    # runs inside the program: an all_gather across the processes)
    step = jax.jit(jax.shard_map(
        q1_distributed_step,
        mesh=mesh,
        in_specs=(P(EXEC_AXIS),),
        out_specs=P(),
        check_vma=False,
    ))
    result = step(sharded)
    cols = [np.asarray(c.data) for c in result.columns]
    valids = [np.asarray(c.valid_mask()) for c in result.columns]

    got = {}
    for r in range(result.num_rows):
        if not (valids[0][r] and valids[1][r]):
            continue  # the all-null-key group and the budget's padding
        key = (int(cols[0][r]), int(cols[1][r]))
        assert key not in got, f"key {key} twice"
        got[key] = {
            "sum_qty": int(cols[2][r]),
            "sum_base_price": int(cols[3][r]),
            "sum_disc_price": int(cols[4][r]),
            "sum_charge": int(cols[5][r]),
            "count": int(cols[9][r]),
        }

    oracle = tpch_q1_numpy(full)
    assert set(got) == set(oracle), (
        f"group keys diverge: extra={set(got) - set(oracle)} "
        f"missing={set(oracle) - set(got)}"
    )
    for key, want in oracle.items():
        g = got[key]
        for field in ("sum_qty", "sum_base_price", "sum_disc_price",
                      "sum_charge", "count"):
            assert g[field] == want[field], (key, field, g[field],
                                             want[field])
    # string columns: per-process max widths DIFFER (pid 0: short, pid 1:
    # long) — shard_table_multiprocess must allgather the global width or
    # the processes build mismatched programs
    from spark_rapids_jni_tpu import types as t

    svals = [f"p{pid}" + "x" * (3 * pid) for _ in range(4)]
    scol = Table([Column.from_pylist(svals, t.STRING)])
    sglobal = shard_table_multiprocess(scol, mesh)
    schars = np.asarray(multihost_utils.process_allgather(
        sglobal.column(0).chars, tiled=True))
    slens = np.asarray(multihost_utils.process_allgather(
        sglobal.column(0).data, tiled=True))
    got_strs = [
        bytes(schars[i, :slens[i]]).decode() for i in range(len(slens))
    ]
    want = [f"p{q}" + "x" * (3 * q) for q in range(n_procs)
            for _ in range(4)]
    assert got_strs == want, (got_strs, want)

    print(f"Q1_MULTIPROC_MATCH pid={pid} groups={len(got)} "
          f"devices={n_global_devices}", flush=True)


if __name__ == "__main__":
    main()
