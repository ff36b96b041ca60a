"""Worker body for the tools/launch.py round-trip smoke (ISSUE 11
satellite): every rank must see the SAME cluster_env() the launcher
wired, the distributed bootstrap must complete (bounded — never a
hang), and a dist.barrier() must release all ranks.

Run via tools/launch.py by tests/test_pod.py; NOT collected by pytest.
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# the suite runs on the CPU whatever the environment says
jax.config.update("jax_platforms", "cpu")


def main():
    outdir = sys.argv[1]
    from mxnet_tpu.parallel import dist

    env = dist.cluster_env()
    assert env is not None, "launcher did not set the DMLC_* protocol"
    assert env["num_workers"] == int(os.environ["DMLC_NUM_WORKER"])
    assert env["rank"] == int(os.environ["DMLC_WORKER_ID"])

    dist.initialize()
    assert dist.is_initialized()
    assert dist.rank() == env["rank"]
    assert dist.num_workers() == env["num_workers"]

    dist.barrier()          # every rank must pass, or nothing returns

    with open(os.path.join(outdir, "env_rank%d.json" % env["rank"]),
              "w") as f:
        json.dump(env, f)

    dist.barrier()          # all records durable before anyone exits
    print("launch worker rank %d/%d OK"
          % (env["rank"], env["num_workers"]), flush=True)


if __name__ == "__main__":
    main()
