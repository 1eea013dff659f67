"""Set-up, training: the longest any rank was seen in state TRAINING (k-means,
PQ codebooks), polled from this process every 0.1 s."""


def read(obs):
    return obs["setup"]["train_s"]
