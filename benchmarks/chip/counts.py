"""The operations and HBM bytes the algorithms require, from shapes.

These count the work the Tsetlin machine and the MLP need, whatever
implements them.  Padding, lane-replicated weights and pre-drawn coin
planes are not work, so a change that removes them reads as a gain and
never as less work.

Tsetlin machine (C classes, m clauses a class, L = 2·o literals):

* one training sample-step updates two clause banks (the target class
  and one sampled negative class).  Each role checks every literal of
  every clause once (m·L literal checks, the clause outputs) and steps
  every automaton of the bank at most once (m·L transitions):
  ``2 · (m·L + m·L)`` operations;
* one prediction checks every literal of every clause of every class:
  ``C·m·L`` operations (the vote sum over C·m clause outputs is lower
  order and not counted);
* the least HBM traffic of a client-epoch is its TA bank read once and
  written once, at the one byte a state needs (states lie in
  [1, 2·n_states] ≤ 254): ``2 · C·m·L`` bytes.  The sample literals
  (S·L bits) are lower order and counted too;
* a prediction needs each distinct model's include mask (one bit a
  literal) and its weights (four bytes a clause) read once:
  ``U · (C·m·L / 8 + 4·C·m)`` bytes for U distinct clients.

MLP (n_in → n_hidden → n_out, P = n_in·n_hidden + n_hidden +
n_hidden·n_out + n_out parameters): a training sample costs ``6·P``
FLOPs (forward 2·P, backward 4·P); an evaluated sample ``2·P``.
"""
from __future__ import annotations


def tm_train_ops(clients: int, epochs: int, samples: int, m: int,
                 L: int) -> int:
    """Literal checks + automaton transitions of a cohort's local epochs."""
    return clients * epochs * samples * 2 * (m * L + m * L)


def tm_train_bytes(clients: int, epochs: int, samples: int, C: int,
                   m: int, L: int) -> int:
    """TA banks read and written once per client-epoch, 1 B a state, plus
    each epoch's literal bits."""
    return clients * epochs * (2 * C * m * L + samples * L // 8)


def tm_predict_ops(predictions: int, C: int, m: int, L: int) -> int:
    return predictions * C * m * L


def tm_predict_bytes(distinct_clients: int, C: int, m: int, L: int) -> int:
    return distinct_clients * (C * m * L // 8 + 4 * C * m)


def tm_round_ops(cohort: int, population: int, epochs: int, n_train: int,
                 n_conf: int, n_test: int, C: int, m: int, L: int) -> int:
    """One TPFL round: the cohort's training and confidence pass, then the
    population's evaluation."""
    return (tm_train_ops(cohort, epochs, n_train, m, L)
            + tm_predict_ops(cohort * n_conf, C, m, L)
            + tm_predict_ops(population * n_test, C, m, L))


def mlp_params(n_in: int, n_hidden: int, n_out: int) -> int:
    return n_in * n_hidden + n_hidden + n_hidden * n_out + n_out


def mlp_round_flops(cohort: int, population: int, epochs: int,
                    n_train: int, batch: int, n_test: int, n_in: int,
                    n_hidden: int, n_out: int) -> int:
    """One FedAvg round: every client's SGD steps (whole batches only,
    as the local trainer drops the remainder), then the population's
    evaluation."""
    p = mlp_params(n_in, n_hidden, n_out)
    steps = max(n_train // batch, 1)
    train = cohort * epochs * steps * batch * 6 * p
    return train + population * n_test * 2 * p


def least_seconds(ops: float, bytes_: float, op_peak: float,
                  hbm_bw: float) -> float:
    """The least time the chip needs: the operations at the peak rate or
    the bytes at the HBM bandwidth, whichever is longer."""
    return max(ops / op_peak, bytes_ / hbm_bw)
