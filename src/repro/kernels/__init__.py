# Pallas TPU kernels, each beside its pure-jnp oracle in ref.py. The
# codec's are gbatc_project.py (guarantee projection, select and replay)
# and flash_attention.py; the RWKV6, RG-LRU and block-quant kernels belong
# to the seed's LM stack, a leaf due for deletion.
