"""100-client FedAvg, ResNet-18(GN) on a CIFAR-10-shaped federation, bf16
— the cohort chip_smoke.py runs as its stage A. Sized for a TPU chip (a
CPU takes minutes per round).

Run:  python main.py --cf fedml_config.yaml
Four chips, cohort over `data`, params over `fsdp`: add
`mesh_shape: {data: 2, fsdp: 2}` under train_args and call
`fedml_tpu.run_simulation(backend="MESH")`.
"""

import fedml_tpu

if __name__ == "__main__":
    print("FINAL:", fedml_tpu.run_simulation())
