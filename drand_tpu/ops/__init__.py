"""Device programs.  The stages of a verify program, as `jax.named_scope`
names them in every operation's `op_name` (ops/bls.py, ops/pairing.py):
one vocabulary for both signature groups and for the partial-signature
programs, read by whoever reduces a device trace to time per stage."""

DIGEST, SIG_DECODE, H2C, MILLER, FINAL_EXP = STAGES = (
    "digest",       # sha256 of the rows' messages (verify.py:_run_fn)
    "sig_decode",   # decompression and the subgroup check
    "h2c",          # hash to curve, through the affine point
    "miller",       # the shared Miller loop over the pairs
    "final_exp",    # the final exponentiation and the comparison with one
)
