"""Per-host launcher (reference: launcher/launch.py:90).

The reference forks --num_gpus ranks per node with RANK/LOCAL_RANK/
WORLD_SIZE/MASTER_* env. One JAX process drives all local TPU chips, so
here a single child is exec'd with the deepspeed_tpu rendezvous env
(DS_COORDINATOR_ADDRESS/DS_NUM_PROCESSES/DS_PROCESS_ID); signal handling
kills the child tree like the reference's sigkill handler (:176).
"""

import argparse
import os
import signal
import subprocess
import sys

from ..utils.logging import logger
from .runner import decode_world_info


def parse_args(argv=None):
    parser = argparse.ArgumentParser(prog="ds_tpu_launch")
    parser.add_argument("--world_info", required=True,
                        help="base64 {host: slots} map from the runner")
    parser.add_argument("--node_rank", type=int, required=True)
    parser.add_argument("--master_addr", required=True)
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("training_script")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    world = decode_world_info(args.world_info)
    num_hosts = len(world)
    if not (0 <= args.node_rank < num_hosts):
        raise ValueError(f"node_rank {args.node_rank} out of range "
                         f"for {num_hosts} hosts")

    env = dict(os.environ)
    env["DS_COORDINATOR_ADDRESS"] = f"{args.master_addr}:{args.master_port}"
    env["DS_NUM_PROCESSES"] = str(num_hosts)
    env["DS_PROCESS_ID"] = str(args.node_rank)
    # reference-compatible aliases some user scripts read
    env["RANK"] = str(args.node_rank)
    env["WORLD_SIZE"] = str(num_hosts)
    env["MASTER_ADDR"] = args.master_addr
    env["MASTER_PORT"] = str(args.master_port)

    cmd = [sys.executable, "-u", args.training_script] + args.training_script_args
    logger.info(f"node {args.node_rank}/{num_hosts}: {' '.join(cmd)}")
    from ..utils.host_env import assert_not_holding_chip
    assert_not_holding_chip("the per-host launcher")
    proc = subprocess.Popen(cmd, env=env)

    def _kill(signum, frame):
        logger.info(f"signal {signum}: killing child {proc.pid}")
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        sys.exit(128 + signum)

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)
    return proc.wait()


if __name__ == "__main__":
    sys.exit(main())
