"""TPU-native kernels (Pallas/Mosaic) — this framework's "native tier".

The reference has no native code at all (SURVEY.md §2: 100% Python); here
the hand-written machine-code tier is Pallas kernels compiled by Mosaic for
the TPU's MXU/VPU: the paged attention kernels (paged_attention.py), the
latent attention kernels (latent_attention.py), a learned indexer's
selection over the page pool (sparse_attention.py), the grouped expert
product (grouped_experts.py) and the delta rule's one-token state update
(delta_update.py).
"""
