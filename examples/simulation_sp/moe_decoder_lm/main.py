"""FedAvg fine-tune of a sparse decoder LM (``model: moe_decoder``): 2 of
8 silos a round, window and full grouped-KV layers, drop-less top-4
routing over a held half of 16 experts. Each evaluation round's record
carries the expert layer's counters (``moe_local_hits``,
``moe_expert_tokens_max`` / ``_mean``, ``moe_dropped`` = 0).

Run:  python main.py --cf fedml_config.yaml
"""

import fedml_tpu

if __name__ == "__main__":
    print("FINAL:", fedml_tpu.run_simulation())
