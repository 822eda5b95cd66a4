"""Long-context LM training — one line.

No reference counterpart (its only sequence models are small LSTMs,
SURVEY.md §2.9): this is the TPU-first long-context path — the
sequence axis sharded over the mesh's ``sp`` axis.

- ``sp_strategy: "ring"`` (this config): K/V blocks rotate over ICI
  via ``ppermute`` with a blockwise online softmax — per-chip score
  panels are O(T/sp x T/sp); the full [T, T] matrix never exists.
- ``sp_strategy: "ulysses"``: all-to-all head re-sharding; the
  per-chip attention for each head group runs the pallas flash kernel
  (``fedml_tpu/ops/flash_attention.py``), so even the gathered
  sequence never materializes its score matrix. Needs
  ``num_heads % sp == 0`` and a ``seq_len`` that is a multiple of 128
  (the kernel's tiling — anything else raises) — this config ships
  num_heads: 8 and seq_len: 256 so flipping the strategy alone works.

Run:  python main.py --cf fedml_config.yaml
Try:  sp_strategy: "ulysses"
      mesh_shape: {dp: 2, sp: 4}  (batch sharded across replicas)
      seq_len: 4096               (drives the stand-in data length)
"""

import fedml_tpu

if __name__ == "__main__":
    print("FINAL:", fedml_tpu.run_distributed())
