"""End-to-end DFL training on the TPU path (deliverable b: the e2e
driver).  FedLay clients — ``--clients-per-device`` on every device —
train a small LM on non-iid token shards for a few hundred steps; model
sync is the paper's 2L-ppermute FedLay mixing.  Compare against
centralized all-reduce:

  python examples/dfl_train.py --steps 300
  python examples/dfl_train.py --steps 300 --sync allreduce

On the CPU, 8 forced host devices stand in for chips:

  JAX_PLATFORMS=cpu python examples/dfl_train.py --steps 300
"""

import os
import sys

if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.train import main as train_main  # noqa: E402

if __name__ == "__main__":
    # --clients defaults to --clients-per-device × devices
    if "--steps" not in sys.argv:
        sys.argv += ["--steps", "300"]
    sys.exit(train_main())
