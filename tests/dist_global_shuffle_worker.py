"""Global-shuffle exchange worker, one per trainer, launched by
``launch_collective`` (ref: Dataset::GlobalShuffle's trainer-to-trainer
redistribution, data_set.h:82-92). Each trainer loads a DISJOINT file,
so the wire exchange is load-bearing: samples each trainer ends up
owning must come from BOTH files."""

import json
import os
import sys

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

import numpy as np  # noqa: E402


def main():
    data_dir, out_base = sys.argv[1], sys.argv[2]
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    out_path = f"{out_base}.rank{rank}.json"
    from paddle_tpu.dataio import DatasetFactory
    from paddle_tpu.distributed import fleet
    fleet.init()       # PaddleCloudRoleMaker reads the launcher env
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    assert fleet.worker_num() == world
    assert len(fleet.worker_endpoints()) == world

    ds = DatasetFactory().create_dataset("InMemoryDataset")
    # DISJOINT per-trainer filelist: the exchange must move samples
    ds.set_filelist([os.path.join(data_dir, f"part-{rank}")])
    ds.set_batch_size(4)
    ds.set_thread(1)
    ds.set_use_var([("x", "float32"), ("y", "float32")])
    ds.load_into_memory()
    n_loaded = ds.get_memory_data_size()
    ds.global_shuffle(fleet=fleet, seed=7)

    owned = sorted(float(s[1][0]) for s in ds._samples)
    with open(out_path, "w") as f:
        json.dump({"rank": rank, "loaded": n_loaded,
                   "owned_labels": owned}, f)


if __name__ == "__main__":
    main()
