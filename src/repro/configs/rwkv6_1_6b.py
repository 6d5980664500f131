"""RWKV-6 "Finch" 1.6B (RWKV-x060-World-1B6): 24 layers, d 2048, 32 heads
of 64, channel mix 7168 (int(3.5·2048)), vocabulary 65,536 with an untied
head, D_MIX_LORA 32, D_DECAY_LORA 64, ``ln_x`` eps 64e-5, ``ln0`` after
the embedding. [arXiv:2404.05892 §4; github.com/BlinkDL/RWKV-LM
``RWKV-v5/src/model.py`` ``RWKV_Tmix_x060`` / ``RWKV_CMix_x060``]

The block follows the published equations (``repro.models.rwkv6``); its
departures (initialisation by ``dense_init``, bf16 activations, no
dropout) are listed there. Embedding and head are stored in bf16, every
other leaf in float32.
"""

from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="rwkv6-1.6b", family="ssm",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=32,  # heads = d/64
    d_ff=7168, vocab_size=65536,
    rwkv=True, rwkv_head_size=64, rwkv_mix_lora=32, rwkv_decay_lora=64,
    source="arXiv:2404.05892",
)
