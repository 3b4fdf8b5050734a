"""Layer "verb": seconds of the traced ``train.run`` that lie in none
of its leaf spans — host time the program does not name. More than 2 %
of the verb means a span is missing."""

import spans


def read(obs):
    return spans.untraced_seconds(spans.tree_of(obs))
