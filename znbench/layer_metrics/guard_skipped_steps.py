"""Steps whose update the anomaly guard skipped inside the window
(non-finite loss or gradient): the growth of
``znicz_step_anomalies_total``, which the decision feeds from the
guard's on-device totals at each guard read.  0 in a correct run: a
skipped step is fast and trains nothing."""


def read(obs):
    return obs.counters.get("znicz_step_anomalies_total")
