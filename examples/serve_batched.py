"""Batched serving example — both serving modes of repro.launch.serve.

LM mode (default): prefill a batch of prompts, then run the decode loop
with donated KV caches (works for every arch family: attention KV, MLA
compressed cache, mamba / rwkv recurrent state).

  PYTHONPATH=src python examples/serve_batched.py --arch rwkv6-1.6b

GNN mode (--gnn): drain a graph request queue through fixed-shape packed
GraphBatch programs, optionally sharded across a device mesh
(docs/SERVING.md documents the full request lifecycle).

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  PYTHONPATH=src python examples/serve_batched.py --gnn --conv gcn \\
      --requests 256 --shards 4
"""
import argparse
import sys

from repro.core import convs as Cv
from repro.launch import serve

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="qwen3-8b")
ap.add_argument("--batch", type=int, default=4)
ap.add_argument("--gen", type=int, default=48)
ap.add_argument("--gnn", action="store_true",
                help="packed GraphBatch GNN serving instead of LM decode")
ap.add_argument("--conv", default="gcn",
                choices=list(Cv.STACK_CONVS))
ap.add_argument("--requests", type=int, default=256)
ap.add_argument("--batch-graphs", type=int, default=32)
ap.add_argument("--precision", default="fp32",
                choices=["fp32", "bf16", "int8"])
ap.add_argument("--shards", type=int, default=1,
                help="data-parallel device shards (needs >= N devices)")
args = ap.parse_args()

if args.gnn:
    sys.argv = ["serve", "--gnn", "--conv", args.conv,
                "--requests", str(args.requests),
                "--batch-graphs", str(args.batch_graphs),
                "--precision", args.precision,
                "--shards", str(args.shards)]
else:
    sys.argv = ["serve", "--arch", args.arch, "--reduced",
                "--batch", str(args.batch), "--prompt-len", "32",
                "--gen", str(args.gen)]
serve.main()
