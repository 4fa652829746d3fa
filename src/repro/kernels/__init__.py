# Hand-written Mosaic kernels that the served models run, each beside the
# jnp form it is checked against: decode_attention.py (the KV-cache decode
# step) and ssd_state_step.py (the Mamba2 state step). models/ picks
# between a kernel and its jnp form (RunConfig.backend). ref.py holds the
# plain jnp oracles the tests compare the model paths with. Add a kernel
# here only for a path a served model runs, with its jnp form beside it.
