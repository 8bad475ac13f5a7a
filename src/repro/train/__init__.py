"""Training substrate of the neural-operator consumer: optimizers, a
fault-tolerant loop, checkpoints."""
