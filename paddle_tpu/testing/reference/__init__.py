"""Plain references: a model's forward pass, loss and gradients in
straightforward ``jax.numpy``, independent of the ops under test."""
