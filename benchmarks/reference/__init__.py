"""The benchmark's plain reference: a Groth16 verifier over BN254 in Python
integers.  It imports nothing of zkp2p_tpu and nothing of JAX, so worker
processes can load it without touching the chip."""
