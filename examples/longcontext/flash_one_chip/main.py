"""GPT-2-small-shaped LM, 1,024 tokens, bf16, flash attention on one
chip (``attention_impl: flash`` -> ``ops/flash_attention.py``). The
kernel is compiled by Mosaic on a TPU and runs in the Pallas interpreter
on a CPU, where this size takes a long time — shrink num_layers /
embed_dim there, keeping seq_len a multiple of 128.

Run:  python main.py --cf fedml_config.yaml
"""

import fedml_tpu

if __name__ == "__main__":
    print("FINAL:", fedml_tpu.run_distributed())
