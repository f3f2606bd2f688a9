"""Share of the window the training loop spent blocked on its batch source; from the trainer's `TimerInfo` `wait`."""


def read(facts):
    return 100.0 * facts["counters"]["feed_wait_s"] / facts["window_s"]
