# launch entry points: mesh.py (topology), dryrun.py (multi-pod lowering),
# compile_cache.py (where the persistent compile cache lives),
# train.py / serve.py (drivers).  Import lazily — dryrun must set XLA_FLAGS
# before any jax import.
