"""Search events at the Expedia Hotel Recommendations set's shape, made from a
seed.

The source (Kaggle, 2016, ``train.csv``) has one row per user search event and
asks for ``hotel_cluster``, one of 100 classes.  There is no network here, so
nothing of it is read and everything below is ``assumed`` in the
configuration's file:

- 11 numeric columns: ``date_time`` in whole hours since 1970 over 2013-2014;
  ``srch_ci`` and ``srch_co`` in whole days (a log-normal lead time and stay,
  2 % of both missing); ``orig_destination_distance`` (log-normal, 36 %
  missing); the flags ``is_mobile``, ``is_package``, ``is_booking``; the
  counts ``srch_adults_cnt``, ``srch_children_cnt``, ``srch_rm_cnt``, ``cnt``;
- 11 categorical columns, ids ``0 ... card - 1`` drawn Zipf-like
  (``floor(u ** 3 * card)``, so id 0 is the most frequent) at the cardinalities
  of ``CAT_CARD``; over 255 of them the rarer ids fall to the missing bin;
- the label: a draw from the softmax of ``PRIOR`` (a skewed class prior) plus
  seeded per-category effects of ``hotel_market``, ``hotel_continent``,
  ``srch_destination_type_id`` and ``hotel_country`` and per-class slopes on
  ``is_package``, the log distance and the children count.  It is drawn
  (Gumbel-max), never the argmax of a column the trees read, so no class ties
  with another on the rows of a leaf.

``chunk`` is pure ``jax.numpy``, a function of the key and the chunk index
alone.
"""

import jax
import jax.numpy as jnp
import numpy as np

NUM_CLASSES = 100
NUMERIC = (
    "date_time", "srch_ci", "srch_co", "orig_destination_distance", "is_mobile", "is_package",
    "srch_adults_cnt", "srch_children_cnt", "srch_rm_cnt", "is_booking", "cnt",
)
CATEGORICAL_NAMES = (
    "site_name", "posa_continent", "user_location_country", "user_location_region", "user_location_city",
    "channel", "srch_destination_id", "srch_destination_type_id", "hotel_continent", "hotel_country",
    "hotel_market",
)
CAT_CARD = (53, 5, 239, 1027, 50447, 11, 59455, 10, 7, 213, 2118)
NUM_FEATURES = len(NUMERIC) + len(CATEGORICAL_NAMES)
CATEGORICAL = tuple(range(len(NUMERIC), NUM_FEATURES))

FIRST_HOUR = 376944  # 2013-01-01 00:00 in hours since 1970
HOURS = 2 * 365 * 24
DISTANCE_MISSING = 0.36
DATES_MISSING = 0.02
# class k's prior logit; with the effects below the largest class holds 5-9 % of
# the rows and the smallest 0.17-0.24 % (four seeds, 262,144 rows each)
PRIOR = (-0.6 * np.log1p(np.arange(NUM_CLASSES)) ** 1.1).astype(np.float32)
# (column, scale) of the label's per-category effects
EFFECTS = (("hotel_market", 0.8), ("hotel_continent", 0.4), ("srch_destination_type_id", 0.4), ("hotel_country", 0.4))


def _small_count(u, probs):
    """A count ``0 ... len(probs) - 1`` with those probabilities."""
    return (u[:, None] > jnp.cumsum(jnp.asarray(probs, jnp.float32))[None, :-1]).sum(axis=1).astype(jnp.float32)


def chunk(key, index, rows: int):
    """Chunk ``index`` of the seed's stream: ``(X (rows, 22) f32, y (rows,) f32)``,
    ``y`` the class id."""
    wkey = jax.random.fold_in(key, 20011)
    ks = jax.random.split(jax.random.fold_in(key, index), 16)
    u = jax.random.uniform(ks[0], (rows, 8))
    z = jax.random.normal(ks[1], (rows, 4))

    hour = FIRST_HOUR + jnp.floor(u[:, 0] * HOURS)
    day = jnp.floor(hour / 24.0)
    ci = day + jnp.floor(jnp.exp(2.5 + 1.2 * z[:, 0]))
    co = ci + 1.0 + jnp.floor(jnp.exp(0.6 + 0.6 * z[:, 1]))
    no_dates = jax.random.uniform(ks[2], (rows,)) < DATES_MISSING
    ci, co = jnp.where(no_dates, jnp.nan, ci), jnp.where(no_dates, jnp.nan, co)
    dist = jnp.exp(6.5 + 1.4 * z[:, 2])
    dist = jnp.where(jax.random.uniform(ks[3], (rows,)) < DISTANCE_MISSING, jnp.nan, dist)
    mobile = (u[:, 1] < 0.13).astype(jnp.float32)
    package = (u[:, 2] < 0.25).astype(jnp.float32)
    booking = (u[:, 3] < 0.08).astype(jnp.float32)
    adults = _small_count(u[:, 4], (0.02, 0.2, 0.6, 0.08, 0.07, 0.01, 0.01, 0.004, 0.003, 0.003))
    children = _small_count(u[:, 5], (0.78, 0.1, 0.09, 0.02, 0.006, 0.002, 0.001, 0.0005, 0.0003, 0.0002))
    rooms = 1.0 + _small_count(u[:, 6], (0.9, 0.07, 0.02, 0.005, 0.002, 0.001, 0.001, 0.001))
    cnt = 1.0 + jnp.floor(jnp.exp(0.8 * z[:, 3]) - 0.5).clip(0.0)
    numeric = jnp.stack([hour, ci, co, dist, mobile, package, adults, children, rooms, booking, cnt], axis=1)

    card = jnp.asarray(CAT_CARD, jnp.float32)
    cats = jnp.floor(jax.random.uniform(ks[4], (rows, len(CAT_CARD))) ** 3 * card).astype(jnp.float32)
    X = jnp.concatenate([numeric, cats], axis=1)

    logits = jnp.broadcast_to(jnp.asarray(PRIOR), (rows, NUM_CLASSES))
    for i, (name, scale) in enumerate(EFFECTS):
        c = CATEGORICAL_NAMES.index(name)
        table = scale * jax.random.normal(jax.random.fold_in(wkey, i), (CAT_CARD[c], NUM_CLASSES))
        logits = logits + table[cats[:, c].astype(jnp.int32)]
    slopes = 0.5 * jax.random.normal(jax.random.fold_in(wkey, 99), (3, NUM_CLASSES))
    lead = jnp.stack([package, jnp.nan_to_num(jnp.log(dist) - 6.5, nan=0.0), jnp.minimum(children, 1.0)], axis=1)
    logits = logits + (lead[:, :, None] * slopes[None]).sum(axis=1)  # no matmul: exact at any precision
    y = jnp.argmax(logits + jax.random.gumbel(ks[5], (rows, NUM_CLASSES)), axis=1).astype(jnp.float32)
    return X, y
