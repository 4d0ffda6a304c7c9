# GENERATED FILE - do not edit by hand.
#
# Regenerate with `python -m mmlspark_tpu.codegen` (the codegen
# meta-test diffs this file against the registry - SURVEY.md 2.2;
# the reference's RCodegen emits the same sparklyr-style surface).
#
# Each ml_* function constructs the corresponding Python stage via
# reticulate; fit()/transform() on the returned stage accept R
# data.frames coerced by reticulate.  NULL arguments are omitted
# (the stage keeps its Python-side default).

.mmlspark_tpu_env <- new.env(parent = emptyenv())

.mmlspark_tpu_module <- function() {
  if (is.null(.mmlspark_tpu_env$mod)) {
    if (!requireNamespace("reticulate", quietly = TRUE)) {
      stop("mmlspark_tpu R bindings require the reticulate package")
    }
    .mmlspark_tpu_env$mod <- reticulate::import("mmlspark_tpu")
  }
  .mmlspark_tpu_env$mod
}

#' BestModel (generated wrapper over mmlspark_tpu.automl.search.BestModel)
#' @param all_scores Per-candidate scores
#' @param best_model Winning fitted model
#' @param best_score Winning metric value
#' @export
ml_best_model <- function(
    all_scores = NULL,
    best_model = NULL,
    best_score = NULL) {
  .py_names <- c(
    all_scores = "allScores",
    best_model = "bestModel",
    best_score = "bestScore")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$BestModel, .args)
}

#' FindBestModel (generated wrapper over mmlspark_tpu.automl.search.FindBestModel)
#' @param evaluation_metric Metric name
#' @param label_col Label column
#' @param models Candidate estimators
#' @export
ml_find_best_model <- function(
    evaluation_metric = "accuracy",
    label_col = "label",
    models = NULL) {
  .py_names <- c(
    evaluation_metric = "evaluationMetric",
    label_col = "labelCol",
    models = "models")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$FindBestModel, .args)
}

#' TuneHyperparameters (generated wrapper over mmlspark_tpu.automl.search.TuneHyperparameters)
#' @param estimator Base estimator
#' @param evaluation_metric Metric name
#' @param label_col Label column
#' @param num_folds CV folds
#' @param num_runs Candidates to sample (random search)
#' @param parallelism Concurrent candidate fits
#' @param random_search Random (true) vs grid (false)
#' @param search_space Built hyperparam space
#' @param seed Sampling seed
#' @export
ml_tune_hyperparameters <- function(
    estimator = NULL,
    evaluation_metric = "accuracy",
    label_col = "label",
    num_folds = 3L,
    num_runs = 10L,
    parallelism = 4L,
    random_search = TRUE,
    search_space = NULL,
    seed = 0L) {
  .py_names <- c(
    estimator = "estimator",
    evaluation_metric = "evaluationMetric",
    label_col = "labelCol",
    num_folds = "numFolds",
    num_runs = "numRuns",
    parallelism = "parallelism",
    random_search = "randomSearch",
    search_space = "searchSpace",
    seed = "seed")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TuneHyperparameters, .args)
}

#' TuneHyperparametersModel (generated wrapper over mmlspark_tpu.automl.search.TuneHyperparametersModel)
#' @param all_scores Per-candidate CV scores
#' @param best_metric Winning CV metric
#' @param best_model Winning refit model
#' @param best_params Winning param map
#' @export
ml_tune_hyperparameters_model <- function(
    all_scores = NULL,
    best_metric = NULL,
    best_model = NULL,
    best_params = NULL) {
  .py_names <- c(
    all_scores = "allScores",
    best_metric = "bestMetric",
    best_model = "bestModel",
    best_params = "bestParams")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TuneHyperparametersModel, .args)
}

#' BingImageSearch (generated wrapper over mmlspark_tpu.cognitive.anomaly.BingImageSearch)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param count Results per query
#' @param error_col Column receiving per-row errors
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param q Search query (value or column)
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_bing_image_search <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    count = NULL,
    error_col = "",
    location = "westus",
    output_col = NULL,
    q = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    count = "count",
    error_col = "errorCol",
    location = "location",
    output_col = "outputCol",
    q = "q",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$BingImageSearch, .args)
}

#' DetectEntireSeries (generated wrapper over mmlspark_tpu.cognitive.anomaly.DetectEntireSeries)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param granularity Series granularity
#' @param location Service region, e.g. eastus
#' @param max_anomaly_ratio Max fraction of anomalies
#' @param output_col The name of the output column
#' @param sensitivity Detection sensitivity 0-99
#' @param series Timeseries: list of {timestamp, value} points per row
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_detect_entire_series <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    granularity = NULL,
    location = "westus",
    max_anomaly_ratio = NULL,
    output_col = NULL,
    sensitivity = NULL,
    series = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    granularity = "granularity",
    location = "location",
    max_anomaly_ratio = "maxAnomalyRatio",
    output_col = "outputCol",
    sensitivity = "sensitivity",
    series = "series",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$DetectEntireSeries, .args)
}

#' DetectLastAnomaly (generated wrapper over mmlspark_tpu.cognitive.anomaly.DetectLastAnomaly)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param granularity Series granularity
#' @param location Service region, e.g. eastus
#' @param max_anomaly_ratio Max fraction of anomalies
#' @param output_col The name of the output column
#' @param sensitivity Detection sensitivity 0-99
#' @param series Timeseries: list of {timestamp, value} points per row
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_detect_last_anomaly <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    granularity = NULL,
    location = "westus",
    max_anomaly_ratio = NULL,
    output_col = NULL,
    sensitivity = NULL,
    series = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    granularity = "granularity",
    location = "location",
    max_anomaly_ratio = "maxAnomalyRatio",
    output_col = "outputCol",
    sensitivity = "sensitivity",
    series = "series",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$DetectLastAnomaly, .args)
}

#' FindSimilarFace (generated wrapper over mmlspark_tpu.cognitive.face.FindSimilarFace)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param face_id Query face ID
#' @param face_ids Candidate face IDs (list or csv)
#' @param face_list_id Face list to search
#' @param large_face_list_id Large face list to search
#' @param location Service region, e.g. eastus
#' @param max_num_of_candidates_returned Max matches returned
#' @param mode matchPerson | matchFace
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_find_similar_face <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    face_id = NULL,
    face_ids = NULL,
    face_list_id = NULL,
    large_face_list_id = NULL,
    location = "westus",
    max_num_of_candidates_returned = NULL,
    mode = NULL,
    output_col = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    face_id = "faceId",
    face_ids = "faceIds",
    face_list_id = "faceListId",
    large_face_list_id = "largeFaceListId",
    location = "location",
    max_num_of_candidates_returned = "maxNumOfCandidatesReturned",
    mode = "mode",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$FindSimilarFace, .args)
}

#' GroupFaces (generated wrapper over mmlspark_tpu.cognitive.face.GroupFaces)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param face_ids Face IDs to group (list or csv)
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_group_faces <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    face_ids = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    face_ids = "faceIds",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$GroupFaces, .args)
}

#' IdentifyFaces (generated wrapper over mmlspark_tpu.cognitive.face.IdentifyFaces)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param confidence_threshold Identification confidence threshold
#' @param error_col Column receiving per-row errors
#' @param face_ids Face IDs to identify (list or csv)
#' @param large_person_group_id Target large person group (excludes personGroupId)
#' @param location Service region, e.g. eastus
#' @param max_num_of_candidates_returned Candidates per face
#' @param output_col The name of the output column
#' @param person_group_id Target person group
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_identify_faces <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    confidence_threshold = NULL,
    error_col = "",
    face_ids = NULL,
    large_person_group_id = NULL,
    location = "westus",
    max_num_of_candidates_returned = NULL,
    output_col = NULL,
    person_group_id = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    confidence_threshold = "confidenceThreshold",
    error_col = "errorCol",
    face_ids = "faceIds",
    large_person_group_id = "largePersonGroupId",
    location = "location",
    max_num_of_candidates_returned = "maxNumOfCandidatesReturned",
    output_col = "outputCol",
    person_group_id = "personGroupId",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$IdentifyFaces, .args)
}

#' VerifyFaces (generated wrapper over mmlspark_tpu.cognitive.face.VerifyFaces)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param face_id Face ID (face-to-person mode)
#' @param face_id1 First face ID (face-to-face mode)
#' @param face_id2 Second face ID (face-to-face mode)
#' @param large_person_group_id Large person group (face-to-person)
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param person_group_id Person group (face-to-person)
#' @param person_id Person ID (face-to-person)
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_verify_faces <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    face_id = NULL,
    face_id1 = NULL,
    face_id2 = NULL,
    large_person_group_id = NULL,
    location = "westus",
    output_col = NULL,
    person_group_id = NULL,
    person_id = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    face_id = "faceId",
    face_id1 = "faceId1",
    face_id2 = "faceId2",
    large_person_group_id = "largePersonGroupId",
    location = "location",
    output_col = "outputCol",
    person_group_id = "personGroupId",
    person_id = "personId",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$VerifyFaces, .args)
}

#' SpeechToText (generated wrapper over mmlspark_tpu.cognitive.speech.SpeechToText)
#' @param audio_data Raw audio bytes (value or column)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param format simple | detailed output
#' @param language Recognition language
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param profanity masked | removed | raw
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_speech_to_text <- function(
    audio_data = NULL,
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    format = NULL,
    language = NULL,
    location = "westus",
    output_col = NULL,
    profanity = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    audio_data = "audioData",
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    format = "format",
    language = "language",
    location = "location",
    output_col = "outputCol",
    profanity = "profanity",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$SpeechToText, .args)
}

#' EntityDetector (generated wrapper over mmlspark_tpu.cognitive.text.EntityDetector)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param language Document language
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param text Input text (value or column)
#' @param url Full service URL (overrides location routing)
#' @export
ml_entity_detector <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    language = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    text = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    language = "language",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    text = "text",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$EntityDetector, .args)
}

#' KeyPhraseExtractor (generated wrapper over mmlspark_tpu.cognitive.text.KeyPhraseExtractor)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param language Document language
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param text Input text (value or column)
#' @param url Full service URL (overrides location routing)
#' @export
ml_key_phrase_extractor <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    language = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    text = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    language = "language",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    text = "text",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$KeyPhraseExtractor, .args)
}

#' LanguageDetector (generated wrapper over mmlspark_tpu.cognitive.text.LanguageDetector)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param language Document language
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param text Input text (value or column)
#' @param url Full service URL (overrides location routing)
#' @export
ml_language_detector <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    language = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    text = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    language = "language",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    text = "text",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$LanguageDetector, .args)
}

#' NER (generated wrapper over mmlspark_tpu.cognitive.text.NER)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param language Document language
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param text Input text (value or column)
#' @param url Full service URL (overrides location routing)
#' @export
ml_n_e_r <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    language = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    text = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    language = "language",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    text = "text",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$NER, .args)
}

#' TextSentiment (generated wrapper over mmlspark_tpu.cognitive.text.TextSentiment)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param language Document language
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param text Input text (value or column)
#' @param url Full service URL (overrides location routing)
#' @export
ml_text_sentiment <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    language = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    text = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    language = "language",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    text = "text",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TextSentiment, .args)
}

#' Translate (generated wrapper over mmlspark_tpu.cognitive.text.Translate)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param from_language Source language (optional)
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param text Text to translate
#' @param to_language Target language(s), comma-joined
#' @param url Full service URL (overrides location routing)
#' @export
ml_translate <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    from_language = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    text = NULL,
    to_language = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    from_language = "fromLanguage",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    text = "text",
    to_language = "toLanguage",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$Translate, .args)
}

#' AnalyzeImage (generated wrapper over mmlspark_tpu.cognitive.vision.AnalyzeImage)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param image_bytes Raw image bytes (value or column)
#' @param image_url Image URL (value or column)
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @param visual_features Comma-joined features (Categories,Tags,Description,...)
#' @export
ml_analyze_image <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    image_bytes = NULL,
    image_url = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    url = "",
    visual_features = NULL) {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    image_bytes = "imageBytes",
    image_url = "imageUrl",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    url = "url",
    visual_features = "visualFeatures")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$AnalyzeImage, .args)
}

#' DescribeImage (generated wrapper over mmlspark_tpu.cognitive.vision.DescribeImage)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param image_bytes Raw image bytes (value or column)
#' @param image_url Image URL (value or column)
#' @param location Service region, e.g. eastus
#' @param max_candidates Caption candidates
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_describe_image <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    image_bytes = NULL,
    image_url = NULL,
    location = "westus",
    max_candidates = NULL,
    output_col = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    image_bytes = "imageBytes",
    image_url = "imageUrl",
    location = "location",
    max_candidates = "maxCandidates",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$DescribeImage, .args)
}

#' DetectFace (generated wrapper over mmlspark_tpu.cognitive.vision.DetectFace)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param image_bytes Raw image bytes (value or column)
#' @param image_url Image URL (value or column)
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param return_face_attributes Comma-joined face attributes to return
#' @param return_face_landmarks Return the 27-point landmarks
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_detect_face <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    image_bytes = NULL,
    image_url = NULL,
    location = "westus",
    output_col = NULL,
    return_face_attributes = NULL,
    return_face_landmarks = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    image_bytes = "imageBytes",
    image_url = "imageUrl",
    location = "location",
    output_col = "outputCol",
    return_face_attributes = "returnFaceAttributes",
    return_face_landmarks = "returnFaceLandmarks",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$DetectFace, .args)
}

#' OCR (generated wrapper over mmlspark_tpu.cognitive.vision.OCR)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param detect_orientation Detect text orientation
#' @param error_col Column receiving per-row errors
#' @param image_bytes Raw image bytes (value or column)
#' @param image_url Image URL (value or column)
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_o_c_r <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    detect_orientation = NULL,
    error_col = "",
    image_bytes = NULL,
    image_url = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    detect_orientation = "detectOrientation",
    error_col = "errorCol",
    image_bytes = "imageBytes",
    image_url = "imageUrl",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$OCR, .args)
}

#' TagImage (generated wrapper over mmlspark_tpu.cognitive.vision.TagImage)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Column receiving per-row errors
#' @param image_bytes Raw image bytes (value or column)
#' @param image_url Image URL (value or column)
#' @param location Service region, e.g. eastus
#' @param output_col The name of the output column
#' @param subscription_key API key sent as Ocp-Apim-Subscription-Key
#' @param url Full service URL (overrides location routing)
#' @export
ml_tag_image <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "",
    image_bytes = NULL,
    image_url = NULL,
    location = "westus",
    output_col = NULL,
    subscription_key = NULL,
    url = "") {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    image_bytes = "imageBytes",
    image_url = "imageUrl",
    location = "location",
    output_col = "outputCol",
    subscription_key = "subscriptionKey",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TagImage, .args)
}

#' Pipeline (generated wrapper over mmlspark_tpu.core.pipeline.Pipeline)
#' @param stages The stages of the pipeline
#' @export
ml_pipeline <- function(
    stages = NULL) {
  .py_names <- c(
    stages = "stages")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$Pipeline, .args)
}

#' PipelineModel (generated wrapper over mmlspark_tpu.core.pipeline.PipelineModel)
#' @param stages The fitted stages
#' @export
ml_pipeline_model <- function(
    stages = NULL) {
  .py_names <- c(
    stages = "stages")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$PipelineModel, .args)
}

#' ImageLIME (generated wrapper over mmlspark_tpu.explain.lime.ImageLIME)
#' @param cell_size Superpixel size
#' @param input_col Column to perturb
#' @param kernel_width Proximity kernel width
#' @param model Inner model to explain
#' @param modifier SLIC spatial weight
#' @param n_samples Perturbations per instance
#' @param output_col Explanation weights column
#' @param prediction_col Inner model's output column
#' @param regularization Lasso lambda
#' @param sampling_fraction P(keep superpixel)
#' @param seed Sampling seed
#' @param superpixel_col Output superpixel column
#' @export
ml_image_l_i_m_e <- function(
    cell_size = 16L,
    input_col = NULL,
    kernel_width = 0.75,
    model = NULL,
    modifier = 130.0,
    n_samples = 512L,
    output_col = "weights",
    prediction_col = "prediction",
    regularization = 0.0,
    sampling_fraction = 0.7,
    seed = 0L,
    superpixel_col = "superpixels") {
  .py_names <- c(
    cell_size = "cellSize",
    input_col = "inputCol",
    kernel_width = "kernelWidth",
    model = "model",
    modifier = "modifier",
    n_samples = "nSamples",
    output_col = "outputCol",
    prediction_col = "predictionCol",
    regularization = "regularization",
    sampling_fraction = "samplingFraction",
    seed = "seed",
    superpixel_col = "superpixelCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ImageLIME, .args)
}

#' TabularLIME (generated wrapper over mmlspark_tpu.explain.lime.TabularLIME)
#' @param input_col Column to perturb
#' @param kernel_width Proximity kernel width
#' @param model Inner model to explain
#' @param n_samples Perturbations per instance
#' @param output_col Explanation weights column
#' @param prediction_col Inner model's output column
#' @param regularization Lasso lambda
#' @param seed Sampling seed
#' @export
ml_tabular_l_i_m_e <- function(
    input_col = NULL,
    kernel_width = 0.75,
    model = NULL,
    n_samples = 512L,
    output_col = "weights",
    prediction_col = "prediction",
    regularization = 0.0,
    seed = 0L) {
  .py_names <- c(
    input_col = "inputCol",
    kernel_width = "kernelWidth",
    model = "model",
    n_samples = "nSamples",
    output_col = "outputCol",
    prediction_col = "predictionCol",
    regularization = "regularization",
    seed = "seed")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TabularLIME, .args)
}

#' TabularLIMEModel (generated wrapper over mmlspark_tpu.explain.lime.TabularLIMEModel)
#' @param feature_means Column means
#' @param feature_stds Column stds
#' @param input_col Column to perturb
#' @param kernel_width Proximity kernel width
#' @param model Inner model to explain
#' @param n_samples Perturbations per instance
#' @param output_col Explanation weights column
#' @param prediction_col Inner model's output column
#' @param regularization Lasso lambda
#' @param seed Sampling seed
#' @export
ml_tabular_l_i_m_e_model <- function(
    feature_means = NULL,
    feature_stds = NULL,
    input_col = NULL,
    kernel_width = 0.75,
    model = NULL,
    n_samples = 512L,
    output_col = "weights",
    prediction_col = "prediction",
    regularization = 0.0,
    seed = 0L) {
  .py_names <- c(
    feature_means = "featureMeans",
    feature_stds = "featureStds",
    input_col = "inputCol",
    kernel_width = "kernelWidth",
    model = "model",
    n_samples = "nSamples",
    output_col = "outputCol",
    prediction_col = "predictionCol",
    regularization = "regularization",
    seed = "seed")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TabularLIMEModel, .args)
}

#' SuperpixelTransformer (generated wrapper over mmlspark_tpu.explain.superpixel.SuperpixelTransformer)
#' @param cell_size Approx superpixel size in px
#' @param input_col Image column
#' @param modifier Spatial-vs-color weight
#' @param output_col Superpixel column
#' @export
ml_superpixel_transformer <- function(
    cell_size = 16L,
    input_col = "image",
    modifier = 130.0,
    output_col = "superpixels") {
  .py_names <- c(
    cell_size = "cellSize",
    input_col = "inputCol",
    modifier = "modifier",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$SuperpixelTransformer, .args)
}

#' CleanMissingData (generated wrapper over mmlspark_tpu.featurize.clean.CleanMissingData)
#' @param cleaning_mode Mean|Median|Custom
#' @param custom_value Fill value for Custom mode
#' @param input_cols Columns to impute
#' @param output_cols Output columns
#' @export
ml_clean_missing_data <- function(
    cleaning_mode = "Mean",
    custom_value = NULL,
    input_cols = NULL,
    output_cols = NULL) {
  .py_names <- c(
    cleaning_mode = "cleaningMode",
    custom_value = "customValue",
    input_cols = "inputCols",
    output_cols = "outputCols")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$CleanMissingData, .args)
}

#' CleanMissingDataModel (generated wrapper over mmlspark_tpu.featurize.clean.CleanMissingDataModel)
#' @param cleaning_mode Mean|Median|Custom
#' @param custom_value Fill value for Custom mode
#' @param fill_values column -> fill value
#' @param input_cols Columns to impute
#' @param output_cols Output columns
#' @export
ml_clean_missing_data_model <- function(
    cleaning_mode = "Mean",
    custom_value = NULL,
    fill_values = NULL,
    input_cols = NULL,
    output_cols = NULL) {
  .py_names <- c(
    cleaning_mode = "cleaningMode",
    custom_value = "customValue",
    fill_values = "fillValues",
    input_cols = "inputCols",
    output_cols = "outputCols")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$CleanMissingDataModel, .args)
}

#' DataConversion (generated wrapper over mmlspark_tpu.featurize.convert.DataConversion)
#' @param cols Columns to convert
#' @param convert_to Target type
#' @param date_time_format Format for date conversion
#' @export
ml_data_conversion <- function(
    cols = NULL,
    convert_to = "double",
    date_time_format = "yyyy-MM-dd HH:mm:ss") {
  .py_names <- c(
    cols = "cols",
    convert_to = "convertTo",
    date_time_format = "dateTimeFormat")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$DataConversion, .args)
}

#' Featurize (generated wrapper over mmlspark_tpu.featurize.featurize.Featurize)
#' @param impute_missing Mean-impute numeric NaNs
#' @param input_cols Columns to featurize (default: all but output)
#' @param num_features Hash buckets for free-text columns
#' @param one_hot_encode_categoricals One-hot instead of index-encode
#' @param output_col Assembled vector column
#' @export
ml_featurize <- function(
    impute_missing = TRUE,
    input_cols = NULL,
    num_features = 262144L,
    one_hot_encode_categoricals = TRUE,
    output_col = "features") {
  .py_names <- c(
    impute_missing = "imputeMissing",
    input_cols = "inputCols",
    num_features = "numFeatures",
    one_hot_encode_categoricals = "oneHotEncodeCategoricals",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$Featurize, .args)
}

#' FeaturizeModel (generated wrapper over mmlspark_tpu.featurize.featurize.FeaturizeModel)
#' @param impute_missing Mean-impute numeric NaNs
#' @param input_cols Columns to featurize (default: all but output)
#' @param num_features Hash buckets for free-text columns
#' @param one_hot_encode_categoricals One-hot instead of index-encode
#' @param output_col Assembled vector column
#' @param plan Per-column featurization plan
#' @export
ml_featurize_model <- function(
    impute_missing = TRUE,
    input_cols = NULL,
    num_features = 262144L,
    one_hot_encode_categoricals = TRUE,
    output_col = "features",
    plan = NULL) {
  .py_names <- c(
    impute_missing = "imputeMissing",
    input_cols = "inputCols",
    num_features = "numFeatures",
    one_hot_encode_categoricals = "oneHotEncodeCategoricals",
    output_col = "outputCol",
    plan = "plan")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$FeaturizeModel, .args)
}

#' IndexToValue (generated wrapper over mmlspark_tpu.featurize.indexer.IndexToValue)
#' @param input_col The name of the input column
#' @param output_col The name of the output column
#' @export
ml_index_to_value <- function(
    input_col = NULL,
    output_col = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$IndexToValue, .args)
}

#' ValueIndexer (generated wrapper over mmlspark_tpu.featurize.indexer.ValueIndexer)
#' @param input_col The name of the input column
#' @param output_col The name of the output column
#' @export
ml_value_indexer <- function(
    input_col = NULL,
    output_col = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ValueIndexer, .args)
}

#' ValueIndexerModel (generated wrapper over mmlspark_tpu.featurize.indexer.ValueIndexerModel)
#' @param input_col The name of the input column
#' @param levels Ordered distinct levels
#' @param output_col The name of the output column
#' @export
ml_value_indexer_model <- function(
    input_col = NULL,
    levels = NULL,
    output_col = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    levels = "levels",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ValueIndexerModel, .args)
}

#' TextFeaturizer (generated wrapper over mmlspark_tpu.featurize.text.TextFeaturizer)
#' @param binary Binary term counts
#' @param input_col Text column
#' @param min_doc_freq Min docs for a term to count
#' @param n_gram_length n-gram length
#' @param num_features Hash buckets
#' @param output_col Output vector column
#' @param stop_words Stop word list
#' @param to_lowercase Lowercase before tokenizing
#' @param tokenizer_pattern Token split regex
#' @param use_i_d_f Rescale with inverse document frequency
#' @param use_n_gram Add n-grams
#' @param use_stop_words_remover Drop stop words
#' @param use_tokenizer Regex-tokenize the text
#' @export
ml_text_featurizer <- function(
    binary = FALSE,
    input_col = NULL,
    min_doc_freq = 1L,
    n_gram_length = 2L,
    num_features = 4096L,
    output_col = "features",
    stop_words = NULL,
    to_lowercase = TRUE,
    tokenizer_pattern = "\\s+",
    use_i_d_f = TRUE,
    use_n_gram = FALSE,
    use_stop_words_remover = FALSE,
    use_tokenizer = TRUE) {
  .py_names <- c(
    binary = "binary",
    input_col = "inputCol",
    min_doc_freq = "minDocFreq",
    n_gram_length = "nGramLength",
    num_features = "numFeatures",
    output_col = "outputCol",
    stop_words = "stopWords",
    to_lowercase = "toLowercase",
    tokenizer_pattern = "tokenizerPattern",
    use_i_d_f = "useIDF",
    use_n_gram = "useNGram",
    use_stop_words_remover = "useStopWordsRemover",
    use_tokenizer = "useTokenizer")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TextFeaturizer, .args)
}

#' TextFeaturizerModel (generated wrapper over mmlspark_tpu.featurize.text.TextFeaturizerModel)
#' @param binary Binary term counts
#' @param idf_vector Fitted IDF weights
#' @param input_col Text column
#' @param min_doc_freq Min docs for a term to count
#' @param n_gram_length n-gram length
#' @param num_features Hash buckets
#' @param output_col Output vector column
#' @param stop_words Stop word list
#' @param to_lowercase Lowercase before tokenizing
#' @param tokenizer_pattern Token split regex
#' @param use_i_d_f Rescale with inverse document frequency
#' @param use_n_gram Add n-grams
#' @param use_stop_words_remover Drop stop words
#' @param use_tokenizer Regex-tokenize the text
#' @export
ml_text_featurizer_model <- function(
    binary = FALSE,
    idf_vector = NULL,
    input_col = NULL,
    min_doc_freq = 1L,
    n_gram_length = 2L,
    num_features = 4096L,
    output_col = "features",
    stop_words = NULL,
    to_lowercase = TRUE,
    tokenizer_pattern = "\\s+",
    use_i_d_f = TRUE,
    use_n_gram = FALSE,
    use_stop_words_remover = FALSE,
    use_tokenizer = TRUE) {
  .py_names <- c(
    binary = "binary",
    idf_vector = "idfVector",
    input_col = "inputCol",
    min_doc_freq = "minDocFreq",
    n_gram_length = "nGramLength",
    num_features = "numFeatures",
    output_col = "outputCol",
    stop_words = "stopWords",
    to_lowercase = "toLowercase",
    tokenizer_pattern = "tokenizerPattern",
    use_i_d_f = "useIDF",
    use_n_gram = "useNGram",
    use_stop_words_remover = "useStopWordsRemover",
    use_tokenizer = "useTokenizer")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TextFeaturizerModel, .args)
}

#' HTTPTransformer (generated wrapper over mmlspark_tpu.io.http.http_transformer.HTTPTransformer)
#' @param backoffs Retry backoffs in ms
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param input_col The name of the input column
#' @param output_col The name of the output column
#' @export
ml_h_t_t_p_transformer <- function(
    backoffs = list(100L, 500L, 1000L),
    concurrency = 4L,
    concurrent_timeout = 60.0,
    input_col = NULL,
    output_col = NULL) {
  .py_names <- c(
    backoffs = "backoffs",
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$HTTPTransformer, .args)
}

#' JSONInputParser (generated wrapper over mmlspark_tpu.io.http.http_transformer.JSONInputParser)
#' @param headers Extra headers
#' @param input_col The name of the input column
#' @param method HTTP method
#' @param output_col The name of the output column
#' @param url Target URL
#' @export
ml_j_s_o_n_input_parser <- function(
    headers = NULL,
    input_col = NULL,
    method = "POST",
    output_col = NULL,
    url = NULL) {
  .py_names <- c(
    headers = "headers",
    input_col = "inputCol",
    method = "method",
    output_col = "outputCol",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$JSONInputParser, .args)
}

#' JSONOutputParser (generated wrapper over mmlspark_tpu.io.http.http_transformer.JSONOutputParser)
#' @param input_col The name of the input column
#' @param output_col The name of the output column
#' @export
ml_j_s_o_n_output_parser <- function(
    input_col = NULL,
    output_col = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$JSONOutputParser, .args)
}

#' SimpleHTTPTransformer (generated wrapper over mmlspark_tpu.io.http.http_transformer.SimpleHTTPTransformer)
#' @param concurrency In-flight requests
#' @param concurrent_timeout Per-request timeout (s)
#' @param error_col Error output column
#' @param flatten_output_batches unused (API parity)
#' @param headers Extra headers
#' @param input_col The name of the input column
#' @param method HTTP method
#' @param output_col The name of the output column
#' @param url Target URL
#' @export
ml_simple_h_t_t_p_transformer <- function(
    concurrency = 4L,
    concurrent_timeout = 60.0,
    error_col = "errors",
    flatten_output_batches = FALSE,
    headers = NULL,
    input_col = NULL,
    method = "POST",
    output_col = NULL,
    url = NULL) {
  .py_names <- c(
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout",
    error_col = "errorCol",
    flatten_output_batches = "flattenOutputBatches",
    headers = "headers",
    input_col = "inputCol",
    method = "method",
    output_col = "outputCol",
    url = "url")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$SimpleHTTPTransformer, .args)
}

#' CNTKModel (generated wrapper over mmlspark_tpu.models.cntk_model.CNTKModel)
#' @param batch_input Batch rows before evaluation
#' @param input_col Input column of feature vectors
#' @param input_node Graph input: index (int) or name (str)
#' @param mini_batch_size Rows per inference minibatch
#' @param model_payload Serialized ONNX model bytes
#' @param output_col Output column
#' @param output_node Graph output: index (int) or name (str)
#' @export
ml_c_n_t_k_model <- function(
    batch_input = TRUE,
    input_col = "features",
    input_node = 0L,
    mini_batch_size = 64L,
    model_payload = NULL,
    output_col = "output",
    output_node = 0L) {
  .py_names <- c(
    batch_input = "batchInput",
    input_col = "inputCol",
    input_node = "inputNode",
    mini_batch_size = "miniBatchSize",
    model_payload = "modelPayload",
    output_col = "outputCol",
    output_node = "outputNode")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$CNTKModel, .args)
}

#' ImageFeaturizer (generated wrapper over mmlspark_tpu.models.image_featurizer.ImageFeaturizer)
#' @param center_crop_after_resize Center-crop to the target size
#' @param channel_normalization_means Per-channel means
#' @param channel_normalization_stds Per-channel stds
#' @param color_scale_factor Pixel pre-scale
#' @param cut_output_layers How many output heads to cut: 0 = final output, k = k-th output from the end (featurization taps an earlier head)
#' @param image_height Model input height
#' @param image_width Model input width
#' @param input_col Image column
#' @param mini_batch_size Rows per inference minibatch
#' @param model_payload Serialized ONNX model bytes
#' @param output_col Feature vector column
#' @export
ml_image_featurizer <- function(
    center_crop_after_resize = FALSE,
    channel_normalization_means = NULL,
    channel_normalization_stds = NULL,
    color_scale_factor = 1.0,
    cut_output_layers = 1L,
    image_height = 224L,
    image_width = 224L,
    input_col = "image",
    mini_batch_size = 64L,
    model_payload = NULL,
    output_col = "features") {
  .py_names <- c(
    center_crop_after_resize = "centerCropAfterResize",
    channel_normalization_means = "channelNormalizationMeans",
    channel_normalization_stds = "channelNormalizationStds",
    color_scale_factor = "colorScaleFactor",
    cut_output_layers = "cutOutputLayers",
    image_height = "imageHeight",
    image_width = "imageWidth",
    input_col = "inputCol",
    mini_batch_size = "miniBatchSize",
    model_payload = "modelPayload",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ImageFeaturizer, .args)
}

#' IsolationForest (generated wrapper over mmlspark_tpu.models.isolation_forest.IsolationForest)
#' @param contamination Expected outlier fraction
#' @param features_col Feature vector column
#' @param max_features unused (API parity)
#' @param max_samples Subsample per tree
#' @param num_estimators Trees in the forest
#' @param prediction_col 0/1 outlier column
#' @param random_seed RNG seed
#' @param score_col Anomaly score column
#' @export
ml_isolation_forest <- function(
    contamination = 0.1,
    features_col = "features",
    max_features = 1.0,
    max_samples = 256L,
    num_estimators = 100L,
    prediction_col = "predictedLabel",
    random_seed = 1L,
    score_col = "outlierScore") {
  .py_names <- c(
    contamination = "contamination",
    features_col = "featuresCol",
    max_features = "maxFeatures",
    max_samples = "maxSamples",
    num_estimators = "numEstimators",
    prediction_col = "predictionCol",
    random_seed = "randomSeed",
    score_col = "scoreCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$IsolationForest, .args)
}

#' IsolationForestModel (generated wrapper over mmlspark_tpu.models.isolation_forest.IsolationForestModel)
#' @param contamination Expected outlier fraction
#' @param features_col Feature vector column
#' @param max_features unused (API parity)
#' @param max_samples Subsample per tree
#' @param num_estimators Trees in the forest
#' @param prediction_col 0/1 outlier column
#' @param random_seed RNG seed
#' @param score_col Anomaly score column
#' @param subsample_size psi used at fit time
#' @param threshold Outlier score threshold
#' @param trees Isolation trees
#' @export
ml_isolation_forest_model <- function(
    contamination = 0.1,
    features_col = "features",
    max_features = 1.0,
    max_samples = 256L,
    num_estimators = 100L,
    prediction_col = "predictedLabel",
    random_seed = 1L,
    score_col = "outlierScore",
    subsample_size = 256L,
    threshold = 0.5,
    trees = NULL) {
  .py_names <- c(
    contamination = "contamination",
    features_col = "featuresCol",
    max_features = "maxFeatures",
    max_samples = "maxSamples",
    num_estimators = "numEstimators",
    prediction_col = "predictionCol",
    random_seed = "randomSeed",
    score_col = "scoreCol",
    subsample_size = "subsampleSize",
    threshold = "threshold",
    trees = "trees")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$IsolationForestModel, .args)
}

#' ConditionalKNN (generated wrapper over mmlspark_tpu.models.knn.ConditionalKNN)
#' @param conditioner_col Query-side set of allowed labels
#' @param features_col Feature vector column
#' @param k Neighbors to return
#' @param label_col Index-side condition label column
#' @param leaf_size unused (ball-tree API parity)
#' @param output_col Matches column
#' @param values_col Payload column returned with matches
#' @export
ml_conditional_k_n_n <- function(
    conditioner_col = "conditioner",
    features_col = "features",
    k = 5L,
    label_col = "labels",
    leaf_size = 50L,
    output_col = "output",
    values_col = "values") {
  .py_names <- c(
    conditioner_col = "conditionerCol",
    features_col = "featuresCol",
    k = "k",
    label_col = "labelCol",
    leaf_size = "leafSize",
    output_col = "outputCol",
    values_col = "valuesCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ConditionalKNN, .args)
}

#' ConditionalKNNModel (generated wrapper over mmlspark_tpu.models.knn.ConditionalKNNModel)
#' @param conditioner_col Query-side set of allowed labels
#' @param features_col Feature vector column
#' @param index_features Indexed feature matrix
#' @param index_labels Index-side labels
#' @param index_values Indexed payloads
#' @param k Neighbors to return
#' @param label_col Index-side condition label column
#' @param leaf_size unused (ball-tree API parity)
#' @param output_col Matches column
#' @param values_col Payload column returned with matches
#' @export
ml_conditional_k_n_n_model <- function(
    conditioner_col = "conditioner",
    features_col = "features",
    index_features = NULL,
    index_labels = NULL,
    index_values = NULL,
    k = 5L,
    label_col = "labels",
    leaf_size = 50L,
    output_col = "output",
    values_col = "values") {
  .py_names <- c(
    conditioner_col = "conditionerCol",
    features_col = "featuresCol",
    index_features = "indexFeatures",
    index_labels = "indexLabels",
    index_values = "indexValues",
    k = "k",
    label_col = "labelCol",
    leaf_size = "leafSize",
    output_col = "outputCol",
    values_col = "valuesCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ConditionalKNNModel, .args)
}

#' KNN (generated wrapper over mmlspark_tpu.models.knn.KNN)
#' @param features_col Feature vector column
#' @param k Neighbors to return
#' @param leaf_size unused (ball-tree API parity)
#' @param output_col Matches column
#' @param values_col Payload column returned with matches
#' @export
ml_k_n_n <- function(
    features_col = "features",
    k = 5L,
    leaf_size = 50L,
    output_col = "output",
    values_col = "values") {
  .py_names <- c(
    features_col = "featuresCol",
    k = "k",
    leaf_size = "leafSize",
    output_col = "outputCol",
    values_col = "valuesCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$KNN, .args)
}

#' KNNModel (generated wrapper over mmlspark_tpu.models.knn.KNNModel)
#' @param features_col Feature vector column
#' @param index_features Indexed feature matrix
#' @param index_values Indexed payloads
#' @param k Neighbors to return
#' @param leaf_size unused (ball-tree API parity)
#' @param output_col Matches column
#' @param values_col Payload column returned with matches
#' @export
ml_k_n_n_model <- function(
    features_col = "features",
    index_features = NULL,
    index_values = NULL,
    k = 5L,
    leaf_size = 50L,
    output_col = "output",
    values_col = "values") {
  .py_names <- c(
    features_col = "featuresCol",
    index_features = "indexFeatures",
    index_values = "indexValues",
    k = "k",
    leaf_size = "leafSize",
    output_col = "outputCol",
    values_col = "valuesCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$KNNModel, .args)
}

#' LightGBMClassificationModel (generated wrapper over mmlspark_tpu.models.lightgbm.LightGBMClassificationModel)
#' @param bagging_fraction Row subsample fraction
#' @param bagging_freq Resample bag every k iterations (0 = off)
#' @param bagging_seed Bagging random seed
#' @param boost_from_average Seed scores at the label average
#' @param booster The trained booster
#' @param boosting_type gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
#' @param categorical_slot_indexes Categorical feature indices
#' @param categorical_slot_names Categorical feature names
#' @param default_listen_port Legacy socket-allreduce base port (no-op on TPU)
#' @param device_type Compute placement: tpu|cpu|gpu
#' @param driver_listen_port Legacy driver rendezvous port (no-op on TPU)
#' @param early_stopping_round Early stopping patience (0 = off)
#' @param feature_fraction Feature subsample fraction
#' @param features_col The name of the features column
#' @param grow_policy lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
#' @param hist_merge Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
#' @param hist_quantize Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
#' @param init_score_col Initial (margin) score column
#' @param is_provide_training_metric Record metrics on training data too
#' @param is_unbalance Reweight unbalanced binary labels
#' @param label_col The name of the label column
#' @param lambda_l1 L1 regularization
#' @param lambda_l2 L2 regularization
#' @param leaf_prediction_col Output column of leaf indices
#' @param learning_rate Shrinkage rate
#' @param matrix_type auto|dense|sparse host matrix handling
#' @param max_bin Max feature bins
#' @param max_depth Max tree depth (-1 = unlimited)
#' @param metric Eval metric ('' = objective default)
#' @param min_data_in_leaf Min rows per leaf
#' @param min_sum_hessian_in_leaf Min leaf hessian sum
#' @param model_string Warm-start model string
#' @param num_batches Split training into sequential batches (continuation-trained)
#' @param num_grad_quant_bins LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
#' @param num_iterations Number of boosting iterations
#' @param num_leaves Max leaves per tree
#' @param num_tasks Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
#' @param num_threads Host-side threads for binning (0 = default)
#' @param objective Training objective
#' @param parallelism Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
#' @param predict_backend Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
#' @param prediction_col The name of the prediction column
#' @param probability_col Class probability output column
#' @param quant_train_renew_leaf LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
#' @param raw_prediction_col Raw margin output column
#' @param seed Master random seed
#' @param slot_names Feature vector slot names
#' @param split_batch k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
#' @param stochastic_rounding LightGBM's stochastic_rounding: False rounds gradients to the nearest level
#' @param thresholds Per-class prediction thresholds
#' @param timeout Distributed initialization timeout in seconds
#' @param top_k Top-k features voted per worker in voting_parallel
#' @param use_barrier_execution_mode Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
#' @param use_quantized_grad LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
#' @param validation_indicator_col Boolean column marking validation rows
#' @param verbosity Native verbosity
#' @param weight_col The name of the sample-weight column
#' @export
ml_light_g_b_m_classification_model <- function(
    bagging_fraction = 1.0,
    bagging_freq = 0L,
    bagging_seed = 3L,
    boost_from_average = TRUE,
    booster = NULL,
    boosting_type = "gbdt",
    categorical_slot_indexes = NULL,
    categorical_slot_names = NULL,
    default_listen_port = 12400L,
    device_type = "tpu",
    driver_listen_port = 0L,
    early_stopping_round = 0L,
    feature_fraction = 1.0,
    features_col = "features",
    grow_policy = "lossguide",
    hist_merge = "auto",
    hist_quantize = "off",
    init_score_col = NULL,
    is_provide_training_metric = FALSE,
    is_unbalance = FALSE,
    label_col = "label",
    lambda_l1 = 0.0,
    lambda_l2 = 0.0,
    leaf_prediction_col = "",
    learning_rate = 0.1,
    matrix_type = "auto",
    max_bin = 255L,
    max_depth = -1L,
    metric = "",
    min_data_in_leaf = 20L,
    min_sum_hessian_in_leaf = 0.001,
    model_string = "",
    num_batches = 0L,
    num_grad_quant_bins = 0L,
    num_iterations = 100L,
    num_leaves = 31L,
    num_tasks = 0L,
    num_threads = 0L,
    objective = "regression",
    parallelism = "data_parallel",
    predict_backend = "auto",
    prediction_col = "prediction",
    probability_col = "probability",
    quant_train_renew_leaf = TRUE,
    raw_prediction_col = "rawPrediction",
    seed = 0L,
    slot_names = NULL,
    split_batch = 0L,
    stochastic_rounding = TRUE,
    thresholds = NULL,
    timeout = 1200.0,
    top_k = 20L,
    use_barrier_execution_mode = FALSE,
    use_quantized_grad = FALSE,
    validation_indicator_col = NULL,
    verbosity = 1L,
    weight_col = NULL) {
  .py_names <- c(
    bagging_fraction = "baggingFraction",
    bagging_freq = "baggingFreq",
    bagging_seed = "baggingSeed",
    boost_from_average = "boostFromAverage",
    booster = "booster",
    boosting_type = "boostingType",
    categorical_slot_indexes = "categoricalSlotIndexes",
    categorical_slot_names = "categoricalSlotNames",
    default_listen_port = "defaultListenPort",
    device_type = "deviceType",
    driver_listen_port = "driverListenPort",
    early_stopping_round = "earlyStoppingRound",
    feature_fraction = "featureFraction",
    features_col = "featuresCol",
    grow_policy = "growPolicy",
    hist_merge = "histMerge",
    hist_quantize = "histQuantize",
    init_score_col = "initScoreCol",
    is_provide_training_metric = "isProvideTrainingMetric",
    is_unbalance = "isUnbalance",
    label_col = "labelCol",
    lambda_l1 = "lambdaL1",
    lambda_l2 = "lambdaL2",
    leaf_prediction_col = "leafPredictionCol",
    learning_rate = "learningRate",
    matrix_type = "matrixType",
    max_bin = "maxBin",
    max_depth = "maxDepth",
    metric = "metric",
    min_data_in_leaf = "minDataInLeaf",
    min_sum_hessian_in_leaf = "minSumHessianInLeaf",
    model_string = "modelString",
    num_batches = "numBatches",
    num_grad_quant_bins = "numGradQuantBins",
    num_iterations = "numIterations",
    num_leaves = "numLeaves",
    num_tasks = "numTasks",
    num_threads = "numThreads",
    objective = "objective",
    parallelism = "parallelism",
    predict_backend = "predictBackend",
    prediction_col = "predictionCol",
    probability_col = "probabilityCol",
    quant_train_renew_leaf = "quantTrainRenewLeaf",
    raw_prediction_col = "rawPredictionCol",
    seed = "seed",
    slot_names = "slotNames",
    split_batch = "splitBatch",
    stochastic_rounding = "stochasticRounding",
    thresholds = "thresholds",
    timeout = "timeout",
    top_k = "topK",
    use_barrier_execution_mode = "useBarrierExecutionMode",
    use_quantized_grad = "useQuantizedGrad",
    validation_indicator_col = "validationIndicatorCol",
    verbosity = "verbosity",
    weight_col = "weightCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$LightGBMClassificationModel, .args)
}

#' LightGBMClassifier (generated wrapper over mmlspark_tpu.models.lightgbm.LightGBMClassifier)
#' @param bagging_fraction Row subsample fraction
#' @param bagging_freq Resample bag every k iterations (0 = off)
#' @param bagging_seed Bagging random seed
#' @param boost_from_average Seed scores at the label average
#' @param boosting_type gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
#' @param categorical_slot_indexes Categorical feature indices
#' @param categorical_slot_names Categorical feature names
#' @param default_listen_port Legacy socket-allreduce base port (no-op on TPU)
#' @param device_type Compute placement: tpu|cpu|gpu
#' @param driver_listen_port Legacy driver rendezvous port (no-op on TPU)
#' @param early_stopping_round Early stopping patience (0 = off)
#' @param feature_fraction Feature subsample fraction
#' @param features_col The name of the features column
#' @param grow_policy lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
#' @param hist_merge Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
#' @param hist_quantize Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
#' @param init_score_col Initial (margin) score column
#' @param is_provide_training_metric Record metrics on training data too
#' @param is_unbalance Reweight unbalanced binary labels
#' @param label_col The name of the label column
#' @param lambda_l1 L1 regularization
#' @param lambda_l2 L2 regularization
#' @param leaf_prediction_col Output column of leaf indices
#' @param learning_rate Shrinkage rate
#' @param matrix_type auto|dense|sparse host matrix handling
#' @param max_bin Max feature bins
#' @param max_depth Max tree depth (-1 = unlimited)
#' @param metric Eval metric ('' = objective default)
#' @param min_data_in_leaf Min rows per leaf
#' @param min_sum_hessian_in_leaf Min leaf hessian sum
#' @param model_string Warm-start model string
#' @param num_batches Split training into sequential batches (continuation-trained)
#' @param num_grad_quant_bins LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
#' @param num_iterations Number of boosting iterations
#' @param num_leaves Max leaves per tree
#' @param num_tasks Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
#' @param num_threads Host-side threads for binning (0 = default)
#' @param objective Training objective
#' @param parallelism Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
#' @param predict_backend Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
#' @param prediction_col The name of the prediction column
#' @param probability_col Class probability output column
#' @param quant_train_renew_leaf LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
#' @param raw_prediction_col Raw margin output column
#' @param seed Master random seed
#' @param slot_names Feature vector slot names
#' @param split_batch k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
#' @param stochastic_rounding LightGBM's stochastic_rounding: False rounds gradients to the nearest level
#' @param thresholds Per-class prediction thresholds
#' @param timeout Distributed initialization timeout in seconds
#' @param top_k Top-k features voted per worker in voting_parallel
#' @param use_barrier_execution_mode Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
#' @param use_quantized_grad LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
#' @param validation_indicator_col Boolean column marking validation rows
#' @param verbosity Native verbosity
#' @param weight_col The name of the sample-weight column
#' @export
ml_light_g_b_m_classifier <- function(
    bagging_fraction = 1.0,
    bagging_freq = 0L,
    bagging_seed = 3L,
    boost_from_average = TRUE,
    boosting_type = "gbdt",
    categorical_slot_indexes = NULL,
    categorical_slot_names = NULL,
    default_listen_port = 12400L,
    device_type = "tpu",
    driver_listen_port = 0L,
    early_stopping_round = 0L,
    feature_fraction = 1.0,
    features_col = "features",
    grow_policy = "lossguide",
    hist_merge = "auto",
    hist_quantize = "off",
    init_score_col = NULL,
    is_provide_training_metric = FALSE,
    is_unbalance = FALSE,
    label_col = "label",
    lambda_l1 = 0.0,
    lambda_l2 = 0.0,
    leaf_prediction_col = "",
    learning_rate = 0.1,
    matrix_type = "auto",
    max_bin = 255L,
    max_depth = -1L,
    metric = "",
    min_data_in_leaf = 20L,
    min_sum_hessian_in_leaf = 0.001,
    model_string = "",
    num_batches = 0L,
    num_grad_quant_bins = 0L,
    num_iterations = 100L,
    num_leaves = 31L,
    num_tasks = 0L,
    num_threads = 0L,
    objective = "binary",
    parallelism = "data_parallel",
    predict_backend = "auto",
    prediction_col = "prediction",
    probability_col = "probability",
    quant_train_renew_leaf = TRUE,
    raw_prediction_col = "rawPrediction",
    seed = 0L,
    slot_names = NULL,
    split_batch = 0L,
    stochastic_rounding = TRUE,
    thresholds = NULL,
    timeout = 1200.0,
    top_k = 20L,
    use_barrier_execution_mode = FALSE,
    use_quantized_grad = FALSE,
    validation_indicator_col = NULL,
    verbosity = 1L,
    weight_col = NULL) {
  .py_names <- c(
    bagging_fraction = "baggingFraction",
    bagging_freq = "baggingFreq",
    bagging_seed = "baggingSeed",
    boost_from_average = "boostFromAverage",
    boosting_type = "boostingType",
    categorical_slot_indexes = "categoricalSlotIndexes",
    categorical_slot_names = "categoricalSlotNames",
    default_listen_port = "defaultListenPort",
    device_type = "deviceType",
    driver_listen_port = "driverListenPort",
    early_stopping_round = "earlyStoppingRound",
    feature_fraction = "featureFraction",
    features_col = "featuresCol",
    grow_policy = "growPolicy",
    hist_merge = "histMerge",
    hist_quantize = "histQuantize",
    init_score_col = "initScoreCol",
    is_provide_training_metric = "isProvideTrainingMetric",
    is_unbalance = "isUnbalance",
    label_col = "labelCol",
    lambda_l1 = "lambdaL1",
    lambda_l2 = "lambdaL2",
    leaf_prediction_col = "leafPredictionCol",
    learning_rate = "learningRate",
    matrix_type = "matrixType",
    max_bin = "maxBin",
    max_depth = "maxDepth",
    metric = "metric",
    min_data_in_leaf = "minDataInLeaf",
    min_sum_hessian_in_leaf = "minSumHessianInLeaf",
    model_string = "modelString",
    num_batches = "numBatches",
    num_grad_quant_bins = "numGradQuantBins",
    num_iterations = "numIterations",
    num_leaves = "numLeaves",
    num_tasks = "numTasks",
    num_threads = "numThreads",
    objective = "objective",
    parallelism = "parallelism",
    predict_backend = "predictBackend",
    prediction_col = "predictionCol",
    probability_col = "probabilityCol",
    quant_train_renew_leaf = "quantTrainRenewLeaf",
    raw_prediction_col = "rawPredictionCol",
    seed = "seed",
    slot_names = "slotNames",
    split_batch = "splitBatch",
    stochastic_rounding = "stochasticRounding",
    thresholds = "thresholds",
    timeout = "timeout",
    top_k = "topK",
    use_barrier_execution_mode = "useBarrierExecutionMode",
    use_quantized_grad = "useQuantizedGrad",
    validation_indicator_col = "validationIndicatorCol",
    verbosity = "verbosity",
    weight_col = "weightCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$LightGBMClassifier, .args)
}

#' LightGBMRanker (generated wrapper over mmlspark_tpu.models.lightgbm.LightGBMRanker)
#' @param bagging_fraction Row subsample fraction
#' @param bagging_freq Resample bag every k iterations (0 = off)
#' @param bagging_seed Bagging random seed
#' @param boost_from_average Seed scores at the label average
#' @param boosting_type gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
#' @param categorical_slot_indexes Categorical feature indices
#' @param categorical_slot_names Categorical feature names
#' @param default_listen_port Legacy socket-allreduce base port (no-op on TPU)
#' @param device_type Compute placement: tpu|cpu|gpu
#' @param driver_listen_port Legacy driver rendezvous port (no-op on TPU)
#' @param early_stopping_round Early stopping patience (0 = off)
#' @param eval_at NDCG eval positions
#' @param feature_fraction Feature subsample fraction
#' @param features_col The name of the features column
#' @param group_col Query group column
#' @param grow_policy lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
#' @param hist_merge Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
#' @param hist_quantize Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
#' @param init_score_col Initial (margin) score column
#' @param is_provide_training_metric Record metrics on training data too
#' @param is_unbalance Reweight unbalanced binary labels
#' @param label_col The name of the label column
#' @param label_gain Relevance gain per label value
#' @param lambda_l1 L1 regularization
#' @param lambda_l2 L2 regularization
#' @param leaf_prediction_col Output column of leaf indices
#' @param learning_rate Shrinkage rate
#' @param matrix_type auto|dense|sparse host matrix handling
#' @param max_bin Max feature bins
#' @param max_depth Max tree depth (-1 = unlimited)
#' @param max_position NDCG truncation for lambdarank
#' @param metric Eval metric ('' = objective default)
#' @param min_data_in_leaf Min rows per leaf
#' @param min_sum_hessian_in_leaf Min leaf hessian sum
#' @param model_string Warm-start model string
#' @param num_batches Split training into sequential batches (continuation-trained)
#' @param num_grad_quant_bins LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
#' @param num_iterations Number of boosting iterations
#' @param num_leaves Max leaves per tree
#' @param num_tasks Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
#' @param num_threads Host-side threads for binning (0 = default)
#' @param objective Training objective
#' @param parallelism Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
#' @param predict_backend Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
#' @param prediction_col The name of the prediction column
#' @param quant_train_renew_leaf LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
#' @param repartition_by_grouping_column Keep each query group within one worker shard
#' @param seed Master random seed
#' @param slot_names Feature vector slot names
#' @param split_batch k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
#' @param stochastic_rounding LightGBM's stochastic_rounding: False rounds gradients to the nearest level
#' @param timeout Distributed initialization timeout in seconds
#' @param top_k Top-k features voted per worker in voting_parallel
#' @param use_barrier_execution_mode Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
#' @param use_quantized_grad LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
#' @param validation_indicator_col Boolean column marking validation rows
#' @param verbosity Native verbosity
#' @param weight_col The name of the sample-weight column
#' @export
ml_light_g_b_m_ranker <- function(
    bagging_fraction = 1.0,
    bagging_freq = 0L,
    bagging_seed = 3L,
    boost_from_average = TRUE,
    boosting_type = "gbdt",
    categorical_slot_indexes = NULL,
    categorical_slot_names = NULL,
    default_listen_port = 12400L,
    device_type = "tpu",
    driver_listen_port = 0L,
    early_stopping_round = 0L,
    eval_at = list(1L, 2L, 3L, 4L, 5L),
    feature_fraction = 1.0,
    features_col = "features",
    group_col = "group",
    grow_policy = "lossguide",
    hist_merge = "auto",
    hist_quantize = "off",
    init_score_col = NULL,
    is_provide_training_metric = FALSE,
    is_unbalance = FALSE,
    label_col = "label",
    label_gain = NULL,
    lambda_l1 = 0.0,
    lambda_l2 = 0.0,
    leaf_prediction_col = "",
    learning_rate = 0.1,
    matrix_type = "auto",
    max_bin = 255L,
    max_depth = -1L,
    max_position = 20L,
    metric = "",
    min_data_in_leaf = 20L,
    min_sum_hessian_in_leaf = 0.001,
    model_string = "",
    num_batches = 0L,
    num_grad_quant_bins = 0L,
    num_iterations = 100L,
    num_leaves = 31L,
    num_tasks = 0L,
    num_threads = 0L,
    objective = "lambdarank",
    parallelism = "data_parallel",
    predict_backend = "auto",
    prediction_col = "prediction",
    quant_train_renew_leaf = TRUE,
    repartition_by_grouping_column = TRUE,
    seed = 0L,
    slot_names = NULL,
    split_batch = 0L,
    stochastic_rounding = TRUE,
    timeout = 1200.0,
    top_k = 20L,
    use_barrier_execution_mode = FALSE,
    use_quantized_grad = FALSE,
    validation_indicator_col = NULL,
    verbosity = 1L,
    weight_col = NULL) {
  .py_names <- c(
    bagging_fraction = "baggingFraction",
    bagging_freq = "baggingFreq",
    bagging_seed = "baggingSeed",
    boost_from_average = "boostFromAverage",
    boosting_type = "boostingType",
    categorical_slot_indexes = "categoricalSlotIndexes",
    categorical_slot_names = "categoricalSlotNames",
    default_listen_port = "defaultListenPort",
    device_type = "deviceType",
    driver_listen_port = "driverListenPort",
    early_stopping_round = "earlyStoppingRound",
    eval_at = "evalAt",
    feature_fraction = "featureFraction",
    features_col = "featuresCol",
    group_col = "groupCol",
    grow_policy = "growPolicy",
    hist_merge = "histMerge",
    hist_quantize = "histQuantize",
    init_score_col = "initScoreCol",
    is_provide_training_metric = "isProvideTrainingMetric",
    is_unbalance = "isUnbalance",
    label_col = "labelCol",
    label_gain = "labelGain",
    lambda_l1 = "lambdaL1",
    lambda_l2 = "lambdaL2",
    leaf_prediction_col = "leafPredictionCol",
    learning_rate = "learningRate",
    matrix_type = "matrixType",
    max_bin = "maxBin",
    max_depth = "maxDepth",
    max_position = "maxPosition",
    metric = "metric",
    min_data_in_leaf = "minDataInLeaf",
    min_sum_hessian_in_leaf = "minSumHessianInLeaf",
    model_string = "modelString",
    num_batches = "numBatches",
    num_grad_quant_bins = "numGradQuantBins",
    num_iterations = "numIterations",
    num_leaves = "numLeaves",
    num_tasks = "numTasks",
    num_threads = "numThreads",
    objective = "objective",
    parallelism = "parallelism",
    predict_backend = "predictBackend",
    prediction_col = "predictionCol",
    quant_train_renew_leaf = "quantTrainRenewLeaf",
    repartition_by_grouping_column = "repartitionByGroupingColumn",
    seed = "seed",
    slot_names = "slotNames",
    split_batch = "splitBatch",
    stochastic_rounding = "stochasticRounding",
    timeout = "timeout",
    top_k = "topK",
    use_barrier_execution_mode = "useBarrierExecutionMode",
    use_quantized_grad = "useQuantizedGrad",
    validation_indicator_col = "validationIndicatorCol",
    verbosity = "verbosity",
    weight_col = "weightCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$LightGBMRanker, .args)
}

#' LightGBMRankerModel (generated wrapper over mmlspark_tpu.models.lightgbm.LightGBMRankerModel)
#' @param bagging_fraction Row subsample fraction
#' @param bagging_freq Resample bag every k iterations (0 = off)
#' @param bagging_seed Bagging random seed
#' @param boost_from_average Seed scores at the label average
#' @param booster The trained booster
#' @param boosting_type gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
#' @param categorical_slot_indexes Categorical feature indices
#' @param categorical_slot_names Categorical feature names
#' @param default_listen_port Legacy socket-allreduce base port (no-op on TPU)
#' @param device_type Compute placement: tpu|cpu|gpu
#' @param driver_listen_port Legacy driver rendezvous port (no-op on TPU)
#' @param early_stopping_round Early stopping patience (0 = off)
#' @param feature_fraction Feature subsample fraction
#' @param features_col The name of the features column
#' @param grow_policy lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
#' @param hist_merge Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
#' @param hist_quantize Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
#' @param init_score_col Initial (margin) score column
#' @param is_provide_training_metric Record metrics on training data too
#' @param is_unbalance Reweight unbalanced binary labels
#' @param label_col The name of the label column
#' @param lambda_l1 L1 regularization
#' @param lambda_l2 L2 regularization
#' @param leaf_prediction_col Output column of leaf indices
#' @param learning_rate Shrinkage rate
#' @param matrix_type auto|dense|sparse host matrix handling
#' @param max_bin Max feature bins
#' @param max_depth Max tree depth (-1 = unlimited)
#' @param metric Eval metric ('' = objective default)
#' @param min_data_in_leaf Min rows per leaf
#' @param min_sum_hessian_in_leaf Min leaf hessian sum
#' @param model_string Warm-start model string
#' @param num_batches Split training into sequential batches (continuation-trained)
#' @param num_grad_quant_bins LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
#' @param num_iterations Number of boosting iterations
#' @param num_leaves Max leaves per tree
#' @param num_tasks Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
#' @param num_threads Host-side threads for binning (0 = default)
#' @param objective Training objective
#' @param parallelism Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
#' @param predict_backend Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
#' @param prediction_col The name of the prediction column
#' @param quant_train_renew_leaf LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
#' @param seed Master random seed
#' @param slot_names Feature vector slot names
#' @param split_batch k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
#' @param stochastic_rounding LightGBM's stochastic_rounding: False rounds gradients to the nearest level
#' @param timeout Distributed initialization timeout in seconds
#' @param top_k Top-k features voted per worker in voting_parallel
#' @param use_barrier_execution_mode Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
#' @param use_quantized_grad LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
#' @param validation_indicator_col Boolean column marking validation rows
#' @param verbosity Native verbosity
#' @param weight_col The name of the sample-weight column
#' @export
ml_light_g_b_m_ranker_model <- function(
    bagging_fraction = 1.0,
    bagging_freq = 0L,
    bagging_seed = 3L,
    boost_from_average = TRUE,
    booster = NULL,
    boosting_type = "gbdt",
    categorical_slot_indexes = NULL,
    categorical_slot_names = NULL,
    default_listen_port = 12400L,
    device_type = "tpu",
    driver_listen_port = 0L,
    early_stopping_round = 0L,
    feature_fraction = 1.0,
    features_col = "features",
    grow_policy = "lossguide",
    hist_merge = "auto",
    hist_quantize = "off",
    init_score_col = NULL,
    is_provide_training_metric = FALSE,
    is_unbalance = FALSE,
    label_col = "label",
    lambda_l1 = 0.0,
    lambda_l2 = 0.0,
    leaf_prediction_col = "",
    learning_rate = 0.1,
    matrix_type = "auto",
    max_bin = 255L,
    max_depth = -1L,
    metric = "",
    min_data_in_leaf = 20L,
    min_sum_hessian_in_leaf = 0.001,
    model_string = "",
    num_batches = 0L,
    num_grad_quant_bins = 0L,
    num_iterations = 100L,
    num_leaves = 31L,
    num_tasks = 0L,
    num_threads = 0L,
    objective = "regression",
    parallelism = "data_parallel",
    predict_backend = "auto",
    prediction_col = "prediction",
    quant_train_renew_leaf = TRUE,
    seed = 0L,
    slot_names = NULL,
    split_batch = 0L,
    stochastic_rounding = TRUE,
    timeout = 1200.0,
    top_k = 20L,
    use_barrier_execution_mode = FALSE,
    use_quantized_grad = FALSE,
    validation_indicator_col = NULL,
    verbosity = 1L,
    weight_col = NULL) {
  .py_names <- c(
    bagging_fraction = "baggingFraction",
    bagging_freq = "baggingFreq",
    bagging_seed = "baggingSeed",
    boost_from_average = "boostFromAverage",
    booster = "booster",
    boosting_type = "boostingType",
    categorical_slot_indexes = "categoricalSlotIndexes",
    categorical_slot_names = "categoricalSlotNames",
    default_listen_port = "defaultListenPort",
    device_type = "deviceType",
    driver_listen_port = "driverListenPort",
    early_stopping_round = "earlyStoppingRound",
    feature_fraction = "featureFraction",
    features_col = "featuresCol",
    grow_policy = "growPolicy",
    hist_merge = "histMerge",
    hist_quantize = "histQuantize",
    init_score_col = "initScoreCol",
    is_provide_training_metric = "isProvideTrainingMetric",
    is_unbalance = "isUnbalance",
    label_col = "labelCol",
    lambda_l1 = "lambdaL1",
    lambda_l2 = "lambdaL2",
    leaf_prediction_col = "leafPredictionCol",
    learning_rate = "learningRate",
    matrix_type = "matrixType",
    max_bin = "maxBin",
    max_depth = "maxDepth",
    metric = "metric",
    min_data_in_leaf = "minDataInLeaf",
    min_sum_hessian_in_leaf = "minSumHessianInLeaf",
    model_string = "modelString",
    num_batches = "numBatches",
    num_grad_quant_bins = "numGradQuantBins",
    num_iterations = "numIterations",
    num_leaves = "numLeaves",
    num_tasks = "numTasks",
    num_threads = "numThreads",
    objective = "objective",
    parallelism = "parallelism",
    predict_backend = "predictBackend",
    prediction_col = "predictionCol",
    quant_train_renew_leaf = "quantTrainRenewLeaf",
    seed = "seed",
    slot_names = "slotNames",
    split_batch = "splitBatch",
    stochastic_rounding = "stochasticRounding",
    timeout = "timeout",
    top_k = "topK",
    use_barrier_execution_mode = "useBarrierExecutionMode",
    use_quantized_grad = "useQuantizedGrad",
    validation_indicator_col = "validationIndicatorCol",
    verbosity = "verbosity",
    weight_col = "weightCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$LightGBMRankerModel, .args)
}

#' LightGBMRegressionModel (generated wrapper over mmlspark_tpu.models.lightgbm.LightGBMRegressionModel)
#' @param bagging_fraction Row subsample fraction
#' @param bagging_freq Resample bag every k iterations (0 = off)
#' @param bagging_seed Bagging random seed
#' @param boost_from_average Seed scores at the label average
#' @param booster The trained booster
#' @param boosting_type gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
#' @param categorical_slot_indexes Categorical feature indices
#' @param categorical_slot_names Categorical feature names
#' @param default_listen_port Legacy socket-allreduce base port (no-op on TPU)
#' @param device_type Compute placement: tpu|cpu|gpu
#' @param driver_listen_port Legacy driver rendezvous port (no-op on TPU)
#' @param early_stopping_round Early stopping patience (0 = off)
#' @param feature_fraction Feature subsample fraction
#' @param features_col The name of the features column
#' @param grow_policy lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
#' @param hist_merge Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
#' @param hist_quantize Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
#' @param init_score_col Initial (margin) score column
#' @param is_provide_training_metric Record metrics on training data too
#' @param is_unbalance Reweight unbalanced binary labels
#' @param label_col The name of the label column
#' @param lambda_l1 L1 regularization
#' @param lambda_l2 L2 regularization
#' @param leaf_prediction_col Output column of leaf indices
#' @param learning_rate Shrinkage rate
#' @param matrix_type auto|dense|sparse host matrix handling
#' @param max_bin Max feature bins
#' @param max_depth Max tree depth (-1 = unlimited)
#' @param metric Eval metric ('' = objective default)
#' @param min_data_in_leaf Min rows per leaf
#' @param min_sum_hessian_in_leaf Min leaf hessian sum
#' @param model_string Warm-start model string
#' @param num_batches Split training into sequential batches (continuation-trained)
#' @param num_grad_quant_bins LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
#' @param num_iterations Number of boosting iterations
#' @param num_leaves Max leaves per tree
#' @param num_tasks Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
#' @param num_threads Host-side threads for binning (0 = default)
#' @param objective Training objective
#' @param parallelism Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
#' @param predict_backend Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
#' @param prediction_col The name of the prediction column
#' @param quant_train_renew_leaf LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
#' @param seed Master random seed
#' @param slot_names Feature vector slot names
#' @param split_batch k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
#' @param stochastic_rounding LightGBM's stochastic_rounding: False rounds gradients to the nearest level
#' @param timeout Distributed initialization timeout in seconds
#' @param top_k Top-k features voted per worker in voting_parallel
#' @param use_barrier_execution_mode Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
#' @param use_quantized_grad LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
#' @param validation_indicator_col Boolean column marking validation rows
#' @param verbosity Native verbosity
#' @param weight_col The name of the sample-weight column
#' @export
ml_light_g_b_m_regression_model <- function(
    bagging_fraction = 1.0,
    bagging_freq = 0L,
    bagging_seed = 3L,
    boost_from_average = TRUE,
    booster = NULL,
    boosting_type = "gbdt",
    categorical_slot_indexes = NULL,
    categorical_slot_names = NULL,
    default_listen_port = 12400L,
    device_type = "tpu",
    driver_listen_port = 0L,
    early_stopping_round = 0L,
    feature_fraction = 1.0,
    features_col = "features",
    grow_policy = "lossguide",
    hist_merge = "auto",
    hist_quantize = "off",
    init_score_col = NULL,
    is_provide_training_metric = FALSE,
    is_unbalance = FALSE,
    label_col = "label",
    lambda_l1 = 0.0,
    lambda_l2 = 0.0,
    leaf_prediction_col = "",
    learning_rate = 0.1,
    matrix_type = "auto",
    max_bin = 255L,
    max_depth = -1L,
    metric = "",
    min_data_in_leaf = 20L,
    min_sum_hessian_in_leaf = 0.001,
    model_string = "",
    num_batches = 0L,
    num_grad_quant_bins = 0L,
    num_iterations = 100L,
    num_leaves = 31L,
    num_tasks = 0L,
    num_threads = 0L,
    objective = "regression",
    parallelism = "data_parallel",
    predict_backend = "auto",
    prediction_col = "prediction",
    quant_train_renew_leaf = TRUE,
    seed = 0L,
    slot_names = NULL,
    split_batch = 0L,
    stochastic_rounding = TRUE,
    timeout = 1200.0,
    top_k = 20L,
    use_barrier_execution_mode = FALSE,
    use_quantized_grad = FALSE,
    validation_indicator_col = NULL,
    verbosity = 1L,
    weight_col = NULL) {
  .py_names <- c(
    bagging_fraction = "baggingFraction",
    bagging_freq = "baggingFreq",
    bagging_seed = "baggingSeed",
    boost_from_average = "boostFromAverage",
    booster = "booster",
    boosting_type = "boostingType",
    categorical_slot_indexes = "categoricalSlotIndexes",
    categorical_slot_names = "categoricalSlotNames",
    default_listen_port = "defaultListenPort",
    device_type = "deviceType",
    driver_listen_port = "driverListenPort",
    early_stopping_round = "earlyStoppingRound",
    feature_fraction = "featureFraction",
    features_col = "featuresCol",
    grow_policy = "growPolicy",
    hist_merge = "histMerge",
    hist_quantize = "histQuantize",
    init_score_col = "initScoreCol",
    is_provide_training_metric = "isProvideTrainingMetric",
    is_unbalance = "isUnbalance",
    label_col = "labelCol",
    lambda_l1 = "lambdaL1",
    lambda_l2 = "lambdaL2",
    leaf_prediction_col = "leafPredictionCol",
    learning_rate = "learningRate",
    matrix_type = "matrixType",
    max_bin = "maxBin",
    max_depth = "maxDepth",
    metric = "metric",
    min_data_in_leaf = "minDataInLeaf",
    min_sum_hessian_in_leaf = "minSumHessianInLeaf",
    model_string = "modelString",
    num_batches = "numBatches",
    num_grad_quant_bins = "numGradQuantBins",
    num_iterations = "numIterations",
    num_leaves = "numLeaves",
    num_tasks = "numTasks",
    num_threads = "numThreads",
    objective = "objective",
    parallelism = "parallelism",
    predict_backend = "predictBackend",
    prediction_col = "predictionCol",
    quant_train_renew_leaf = "quantTrainRenewLeaf",
    seed = "seed",
    slot_names = "slotNames",
    split_batch = "splitBatch",
    stochastic_rounding = "stochasticRounding",
    timeout = "timeout",
    top_k = "topK",
    use_barrier_execution_mode = "useBarrierExecutionMode",
    use_quantized_grad = "useQuantizedGrad",
    validation_indicator_col = "validationIndicatorCol",
    verbosity = "verbosity",
    weight_col = "weightCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$LightGBMRegressionModel, .args)
}

#' LightGBMRegressor (generated wrapper over mmlspark_tpu.models.lightgbm.LightGBMRegressor)
#' @param alpha Quantile/huber alpha
#' @param bagging_fraction Row subsample fraction
#' @param bagging_freq Resample bag every k iterations (0 = off)
#' @param bagging_seed Bagging random seed
#' @param boost_from_average Seed scores at the label average
#' @param boosting_type gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
#' @param categorical_slot_indexes Categorical feature indices
#' @param categorical_slot_names Categorical feature names
#' @param default_listen_port Legacy socket-allreduce base port (no-op on TPU)
#' @param device_type Compute placement: tpu|cpu|gpu
#' @param driver_listen_port Legacy driver rendezvous port (no-op on TPU)
#' @param early_stopping_round Early stopping patience (0 = off)
#' @param feature_fraction Feature subsample fraction
#' @param features_col The name of the features column
#' @param grow_policy lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
#' @param hist_merge Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
#' @param hist_quantize Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
#' @param init_score_col Initial (margin) score column
#' @param is_provide_training_metric Record metrics on training data too
#' @param is_unbalance Reweight unbalanced binary labels
#' @param label_col The name of the label column
#' @param lambda_l1 L1 regularization
#' @param lambda_l2 L2 regularization
#' @param leaf_prediction_col Output column of leaf indices
#' @param learning_rate Shrinkage rate
#' @param matrix_type auto|dense|sparse host matrix handling
#' @param max_bin Max feature bins
#' @param max_depth Max tree depth (-1 = unlimited)
#' @param metric Eval metric ('' = objective default)
#' @param min_data_in_leaf Min rows per leaf
#' @param min_sum_hessian_in_leaf Min leaf hessian sum
#' @param model_string Warm-start model string
#' @param num_batches Split training into sequential batches (continuation-trained)
#' @param num_grad_quant_bins LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
#' @param num_iterations Number of boosting iterations
#' @param num_leaves Max leaves per tree
#' @param num_tasks Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
#' @param num_threads Host-side threads for binning (0 = default)
#' @param objective Training objective
#' @param parallelism Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
#' @param predict_backend Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
#' @param prediction_col The name of the prediction column
#' @param quant_train_renew_leaf LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
#' @param seed Master random seed
#' @param slot_names Feature vector slot names
#' @param split_batch k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
#' @param stochastic_rounding LightGBM's stochastic_rounding: False rounds gradients to the nearest level
#' @param timeout Distributed initialization timeout in seconds
#' @param top_k Top-k features voted per worker in voting_parallel
#' @param tweedie_variance_power Tweedie variance power (1..2)
#' @param use_barrier_execution_mode Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
#' @param use_quantized_grad LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
#' @param validation_indicator_col Boolean column marking validation rows
#' @param verbosity Native verbosity
#' @param weight_col The name of the sample-weight column
#' @export
ml_light_g_b_m_regressor <- function(
    alpha = 0.9,
    bagging_fraction = 1.0,
    bagging_freq = 0L,
    bagging_seed = 3L,
    boost_from_average = TRUE,
    boosting_type = "gbdt",
    categorical_slot_indexes = NULL,
    categorical_slot_names = NULL,
    default_listen_port = 12400L,
    device_type = "tpu",
    driver_listen_port = 0L,
    early_stopping_round = 0L,
    feature_fraction = 1.0,
    features_col = "features",
    grow_policy = "lossguide",
    hist_merge = "auto",
    hist_quantize = "off",
    init_score_col = NULL,
    is_provide_training_metric = FALSE,
    is_unbalance = FALSE,
    label_col = "label",
    lambda_l1 = 0.0,
    lambda_l2 = 0.0,
    leaf_prediction_col = "",
    learning_rate = 0.1,
    matrix_type = "auto",
    max_bin = 255L,
    max_depth = -1L,
    metric = "",
    min_data_in_leaf = 20L,
    min_sum_hessian_in_leaf = 0.001,
    model_string = "",
    num_batches = 0L,
    num_grad_quant_bins = 0L,
    num_iterations = 100L,
    num_leaves = 31L,
    num_tasks = 0L,
    num_threads = 0L,
    objective = "regression",
    parallelism = "data_parallel",
    predict_backend = "auto",
    prediction_col = "prediction",
    quant_train_renew_leaf = TRUE,
    seed = 0L,
    slot_names = NULL,
    split_batch = 0L,
    stochastic_rounding = TRUE,
    timeout = 1200.0,
    top_k = 20L,
    tweedie_variance_power = 1.5,
    use_barrier_execution_mode = FALSE,
    use_quantized_grad = FALSE,
    validation_indicator_col = NULL,
    verbosity = 1L,
    weight_col = NULL) {
  .py_names <- c(
    alpha = "alpha",
    bagging_fraction = "baggingFraction",
    bagging_freq = "baggingFreq",
    bagging_seed = "baggingSeed",
    boost_from_average = "boostFromAverage",
    boosting_type = "boostingType",
    categorical_slot_indexes = "categoricalSlotIndexes",
    categorical_slot_names = "categoricalSlotNames",
    default_listen_port = "defaultListenPort",
    device_type = "deviceType",
    driver_listen_port = "driverListenPort",
    early_stopping_round = "earlyStoppingRound",
    feature_fraction = "featureFraction",
    features_col = "featuresCol",
    grow_policy = "growPolicy",
    hist_merge = "histMerge",
    hist_quantize = "histQuantize",
    init_score_col = "initScoreCol",
    is_provide_training_metric = "isProvideTrainingMetric",
    is_unbalance = "isUnbalance",
    label_col = "labelCol",
    lambda_l1 = "lambdaL1",
    lambda_l2 = "lambdaL2",
    leaf_prediction_col = "leafPredictionCol",
    learning_rate = "learningRate",
    matrix_type = "matrixType",
    max_bin = "maxBin",
    max_depth = "maxDepth",
    metric = "metric",
    min_data_in_leaf = "minDataInLeaf",
    min_sum_hessian_in_leaf = "minSumHessianInLeaf",
    model_string = "modelString",
    num_batches = "numBatches",
    num_grad_quant_bins = "numGradQuantBins",
    num_iterations = "numIterations",
    num_leaves = "numLeaves",
    num_tasks = "numTasks",
    num_threads = "numThreads",
    objective = "objective",
    parallelism = "parallelism",
    predict_backend = "predictBackend",
    prediction_col = "predictionCol",
    quant_train_renew_leaf = "quantTrainRenewLeaf",
    seed = "seed",
    slot_names = "slotNames",
    split_batch = "splitBatch",
    stochastic_rounding = "stochasticRounding",
    timeout = "timeout",
    top_k = "topK",
    tweedie_variance_power = "tweedieVariancePower",
    use_barrier_execution_mode = "useBarrierExecutionMode",
    use_quantized_grad = "useQuantizedGrad",
    validation_indicator_col = "validationIndicatorCol",
    verbosity = "verbosity",
    weight_col = "weightCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$LightGBMRegressor, .args)
}

#' ONNXModel (generated wrapper over mmlspark_tpu.models.onnx_model.ONNXModel)
#' @param arg_max_dict Map input col -> output col to apply argmax to
#' @param device_type Compute placement: tpu|cpu
#' @param feed_dict Map of ONNX graph input name -> DataFrame column
#' @param fetch_dict Map of output DataFrame column -> ONNX graph output name
#' @param mini_batch_size Rows per inference minibatch
#' @param model_payload Serialized ONNX model bytes
#' @param soft_max_dict Map input col -> output col to apply softmax to
#' @export
ml_o_n_n_x_model <- function(
    arg_max_dict = NULL,
    device_type = "tpu",
    feed_dict = NULL,
    fetch_dict = NULL,
    mini_batch_size = 64L,
    model_payload = NULL,
    soft_max_dict = NULL) {
  .py_names <- c(
    arg_max_dict = "argMaxDict",
    device_type = "deviceType",
    feed_dict = "feedDict",
    fetch_dict = "fetchDict",
    mini_batch_size = "miniBatchSize",
    model_payload = "modelPayload",
    soft_max_dict = "softMaxDict")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ONNXModel, .args)
}

#' RankingAdapter (generated wrapper over mmlspark_tpu.models.sar.RankingAdapter)
#' @param k Items to recommend
#' @param label_col Output true-items column
#' @param recommender Inner recommender estimator
#' @export
ml_ranking_adapter <- function(
    k = 10L,
    label_col = "label",
    recommender = NULL) {
  .py_names <- c(
    k = "k",
    label_col = "labelCol",
    recommender = "recommender")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$RankingAdapter, .args)
}

#' RankingAdapterModel (generated wrapper over mmlspark_tpu.models.sar.RankingAdapterModel)
#' @param k Items to recommend
#' @param label_col Output true-items column
#' @param recommender_model Fitted recommender
#' @export
ml_ranking_adapter_model <- function(
    k = 10L,
    label_col = "label",
    recommender_model = NULL) {
  .py_names <- c(
    k = "k",
    label_col = "labelCol",
    recommender_model = "recommenderModel")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$RankingAdapterModel, .args)
}

#' RankingEvaluator (generated wrapper over mmlspark_tpu.models.sar.RankingEvaluator)
#' @param k Cutoff
#' @param label_col True item-list column
#' @param metric_name ndcgAt|map|precisionAtk|recallAtK
#' @param prediction_col Predicted item-list column
#' @export
ml_ranking_evaluator <- function(
    k = 10L,
    label_col = "label",
    metric_name = "ndcgAt",
    prediction_col = "prediction") {
  .py_names <- c(
    k = "k",
    label_col = "labelCol",
    metric_name = "metricName",
    prediction_col = "predictionCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$RankingEvaluator, .args)
}

#' RankingTrainValidationSplit (generated wrapper over mmlspark_tpu.models.sar.RankingTrainValidationSplit)
#' @param estimator Recommender estimator
#' @param item_col Item column
#' @param k Eval cutoff
#' @param seed Split seed
#' @param train_ratio Train fraction per user
#' @param user_col User column
#' @export
ml_ranking_train_validation_split <- function(
    estimator = NULL,
    item_col = "item",
    k = 10L,
    seed = 0L,
    train_ratio = 0.75,
    user_col = "user") {
  .py_names <- c(
    estimator = "estimator",
    item_col = "itemCol",
    k = "k",
    seed = "seed",
    train_ratio = "trainRatio",
    user_col = "userCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$RankingTrainValidationSplit, .args)
}

#' RankingTrainValidationSplitModel (generated wrapper over mmlspark_tpu.models.sar.RankingTrainValidationSplitModel)
#' @param best_model Fitted recommender
#' @param validation_metric Holdout ranking metric
#' @export
ml_ranking_train_validation_split_model <- function(
    best_model = NULL,
    validation_metric = NULL) {
  .py_names <- c(
    best_model = "bestModel",
    validation_metric = "validationMetric")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$RankingTrainValidationSplitModel, .args)
}

#' RecommendationIndexer (generated wrapper over mmlspark_tpu.models.sar.RecommendationIndexer)
#' @param item_input_col Raw item column
#' @param item_output_col Indexed item column
#' @param rating_col Rating column
#' @param user_input_col Raw user column
#' @param user_output_col Indexed user column
#' @export
ml_recommendation_indexer <- function(
    item_input_col = "item",
    item_output_col = "item_idx",
    rating_col = "rating",
    user_input_col = "user",
    user_output_col = "user_idx") {
  .py_names <- c(
    item_input_col = "itemInputCol",
    item_output_col = "itemOutputCol",
    rating_col = "ratingCol",
    user_input_col = "userInputCol",
    user_output_col = "userOutputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$RecommendationIndexer, .args)
}

#' RecommendationIndexerModel (generated wrapper over mmlspark_tpu.models.sar.RecommendationIndexerModel)
#' @param item_input_col Raw item column
#' @param item_levels Item levels
#' @param item_output_col Indexed item column
#' @param user_input_col Raw user column
#' @param user_levels User levels
#' @param user_output_col Indexed user column
#' @export
ml_recommendation_indexer_model <- function(
    item_input_col = "item",
    item_levels = NULL,
    item_output_col = "item_idx",
    user_input_col = "user",
    user_levels = NULL,
    user_output_col = "user_idx") {
  .py_names <- c(
    item_input_col = "itemInputCol",
    item_levels = "itemLevels",
    item_output_col = "itemOutputCol",
    user_input_col = "userInputCol",
    user_levels = "userLevels",
    user_output_col = "userOutputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$RecommendationIndexerModel, .args)
}

#' SAR (generated wrapper over mmlspark_tpu.models.sar.SAR)
#' @param activity_time_format unused (API parity)
#' @param item_col Item id column
#' @param rating_col Rating column ('' = implicit 1.0)
#' @param similarity_function cooccurrence|jaccard|lift
#' @param support_threshold Min co-occurrence count
#' @param time_col Event-time column (unix seconds)
#' @param time_decay_coeff Affinity half-life in days
#' @param user_col User id column
#' @export
ml_s_a_r <- function(
    activity_time_format = "",
    item_col = "item",
    rating_col = "rating",
    similarity_function = "jaccard",
    support_threshold = 4L,
    time_col = "",
    time_decay_coeff = 30L,
    user_col = "user") {
  .py_names <- c(
    activity_time_format = "activityTimeFormat",
    item_col = "itemCol",
    rating_col = "ratingCol",
    similarity_function = "similarityFunction",
    support_threshold = "supportThreshold",
    time_col = "timeCol",
    time_decay_coeff = "timeDecayCoeff",
    user_col = "userCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$SAR, .args)
}

#' SARModel (generated wrapper over mmlspark_tpu.models.sar.SARModel)
#' @param activity_time_format unused (API parity)
#' @param item_col Item id column
#' @param item_levels Item id order
#' @param item_similarity (I, I) similarity
#' @param rating_col Rating column ('' = implicit 1.0)
#' @param similarity_function cooccurrence|jaccard|lift
#' @param support_threshold Min co-occurrence count
#' @param time_col Event-time column (unix seconds)
#' @param time_decay_coeff Affinity half-life in days
#' @param user_affinity (U, I) affinity matrix
#' @param user_col User id column
#' @param user_levels User id order
#' @export
ml_s_a_r_model <- function(
    activity_time_format = "",
    item_col = "item",
    item_levels = NULL,
    item_similarity = NULL,
    rating_col = "rating",
    similarity_function = "jaccard",
    support_threshold = 4L,
    time_col = "",
    time_decay_coeff = 30L,
    user_affinity = NULL,
    user_col = "user",
    user_levels = NULL) {
  .py_names <- c(
    activity_time_format = "activityTimeFormat",
    item_col = "itemCol",
    item_levels = "itemLevels",
    item_similarity = "itemSimilarity",
    rating_col = "ratingCol",
    similarity_function = "similarityFunction",
    support_threshold = "supportThreshold",
    time_col = "timeCol",
    time_decay_coeff = "timeDecayCoeff",
    user_affinity = "userAffinity",
    user_col = "userCol",
    user_levels = "userLevels")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$SARModel, .args)
}

#' VowpalWabbitClassificationModel (generated wrapper over mmlspark_tpu.models.vw.VowpalWabbitClassificationModel)
#' @param batch_size Minibatch size per SGD step
#' @param features_col The name of the features column
#' @param hash_seed Hash seed
#' @param l1 L1 regularization
#' @param l2 L2 regularization
#' @param label_col The name of the label column
#' @param learning_rate SGD learning rate
#' @param loss_function logistic|squared
#' @param num_bits log2 weight-space size
#' @param num_passes Passes over the data
#' @param pass_through_args Raw VW argument string
#' @param power_t LR decay exponent t^-p
#' @param prediction_col The name of the prediction column
#' @param probability_col Probability column
#' @param raw_prediction_col Margin column
#' @param weight_col The name of the sample-weight column
#' @param weights Learned weight vector
#' @export
ml_vowpal_wabbit_classification_model <- function(
    batch_size = 256L,
    features_col = "features",
    hash_seed = 0L,
    l1 = 0.0,
    l2 = 0.0,
    label_col = "label",
    learning_rate = 0.5,
    loss_function = "logistic",
    num_bits = 18L,
    num_passes = 1L,
    pass_through_args = "",
    power_t = 0.5,
    prediction_col = "prediction",
    probability_col = "probability",
    raw_prediction_col = "rawPrediction",
    weight_col = NULL,
    weights = NULL) {
  .py_names <- c(
    batch_size = "batchSize",
    features_col = "featuresCol",
    hash_seed = "hashSeed",
    l1 = "l1",
    l2 = "l2",
    label_col = "labelCol",
    learning_rate = "learningRate",
    loss_function = "lossFunction",
    num_bits = "numBits",
    num_passes = "numPasses",
    pass_through_args = "passThroughArgs",
    power_t = "powerT",
    prediction_col = "predictionCol",
    probability_col = "probabilityCol",
    raw_prediction_col = "rawPredictionCol",
    weight_col = "weightCol",
    weights = "weights")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$VowpalWabbitClassificationModel, .args)
}

#' VowpalWabbitClassifier (generated wrapper over mmlspark_tpu.models.vw.VowpalWabbitClassifier)
#' @param batch_size Minibatch size per SGD step
#' @param features_col The name of the features column
#' @param hash_seed Hash seed
#' @param l1 L1 regularization
#' @param l2 L2 regularization
#' @param label_col The name of the label column
#' @param learning_rate SGD learning rate
#' @param loss_function logistic|squared
#' @param num_bits log2 weight-space size
#' @param num_passes Passes over the data
#' @param pass_through_args Raw VW argument string
#' @param power_t LR decay exponent t^-p
#' @param prediction_col The name of the prediction column
#' @param weight_col The name of the sample-weight column
#' @export
ml_vowpal_wabbit_classifier <- function(
    batch_size = 256L,
    features_col = "features",
    hash_seed = 0L,
    l1 = 0.0,
    l2 = 0.0,
    label_col = "label",
    learning_rate = 0.5,
    loss_function = "logistic",
    num_bits = 18L,
    num_passes = 1L,
    pass_through_args = "",
    power_t = 0.5,
    prediction_col = "prediction",
    weight_col = NULL) {
  .py_names <- c(
    batch_size = "batchSize",
    features_col = "featuresCol",
    hash_seed = "hashSeed",
    l1 = "l1",
    l2 = "l2",
    label_col = "labelCol",
    learning_rate = "learningRate",
    loss_function = "lossFunction",
    num_bits = "numBits",
    num_passes = "numPasses",
    pass_through_args = "passThroughArgs",
    power_t = "powerT",
    prediction_col = "predictionCol",
    weight_col = "weightCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$VowpalWabbitClassifier, .args)
}

#' VowpalWabbitFeaturizer (generated wrapper over mmlspark_tpu.models.vw.VowpalWabbitFeaturizer)
#' @param input_cols Columns to hash
#' @param num_bits log2 of the hashed space
#' @param output_col Hashed vector column
#' @param seed Hash seed
#' @param string_split Split strings into words
#' @param sum_collisions Sum colliding features
#' @export
ml_vowpal_wabbit_featurizer <- function(
    input_cols = NULL,
    num_bits = 18L,
    output_col = "features",
    seed = 0L,
    string_split = FALSE,
    sum_collisions = TRUE) {
  .py_names <- c(
    input_cols = "inputCols",
    num_bits = "numBits",
    output_col = "outputCol",
    seed = "seed",
    string_split = "stringSplit",
    sum_collisions = "sumCollisions")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$VowpalWabbitFeaturizer, .args)
}

#' VowpalWabbitInteractions (generated wrapper over mmlspark_tpu.models.vw.VowpalWabbitInteractions)
#' @param input_cols Vector columns to interact
#' @param num_bits log2 of the hashed space
#' @param output_col Interaction vector column
#' @export
ml_vowpal_wabbit_interactions <- function(
    input_cols = NULL,
    num_bits = 18L,
    output_col = "features") {
  .py_names <- c(
    input_cols = "inputCols",
    num_bits = "numBits",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$VowpalWabbitInteractions, .args)
}

#' VowpalWabbitRegressionModel (generated wrapper over mmlspark_tpu.models.vw.VowpalWabbitRegressionModel)
#' @param batch_size Minibatch size per SGD step
#' @param features_col The name of the features column
#' @param hash_seed Hash seed
#' @param l1 L1 regularization
#' @param l2 L2 regularization
#' @param label_col The name of the label column
#' @param learning_rate SGD learning rate
#' @param loss_function logistic|squared
#' @param num_bits log2 weight-space size
#' @param num_passes Passes over the data
#' @param pass_through_args Raw VW argument string
#' @param power_t LR decay exponent t^-p
#' @param prediction_col The name of the prediction column
#' @param weight_col The name of the sample-weight column
#' @param weights Learned weight vector
#' @export
ml_vowpal_wabbit_regression_model <- function(
    batch_size = 256L,
    features_col = "features",
    hash_seed = 0L,
    l1 = 0.0,
    l2 = 0.0,
    label_col = "label",
    learning_rate = 0.5,
    loss_function = "logistic",
    num_bits = 18L,
    num_passes = 1L,
    pass_through_args = "",
    power_t = 0.5,
    prediction_col = "prediction",
    weight_col = NULL,
    weights = NULL) {
  .py_names <- c(
    batch_size = "batchSize",
    features_col = "featuresCol",
    hash_seed = "hashSeed",
    l1 = "l1",
    l2 = "l2",
    label_col = "labelCol",
    learning_rate = "learningRate",
    loss_function = "lossFunction",
    num_bits = "numBits",
    num_passes = "numPasses",
    pass_through_args = "passThroughArgs",
    power_t = "powerT",
    prediction_col = "predictionCol",
    weight_col = "weightCol",
    weights = "weights")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$VowpalWabbitRegressionModel, .args)
}

#' VowpalWabbitRegressor (generated wrapper over mmlspark_tpu.models.vw.VowpalWabbitRegressor)
#' @param batch_size Minibatch size per SGD step
#' @param features_col The name of the features column
#' @param hash_seed Hash seed
#' @param l1 L1 regularization
#' @param l2 L2 regularization
#' @param label_col The name of the label column
#' @param learning_rate SGD learning rate
#' @param loss_function logistic|squared
#' @param num_bits log2 weight-space size
#' @param num_passes Passes over the data
#' @param pass_through_args Raw VW argument string
#' @param power_t LR decay exponent t^-p
#' @param prediction_col The name of the prediction column
#' @param weight_col The name of the sample-weight column
#' @export
ml_vowpal_wabbit_regressor <- function(
    batch_size = 256L,
    features_col = "features",
    hash_seed = 0L,
    l1 = 0.0,
    l2 = 0.0,
    label_col = "label",
    learning_rate = 0.5,
    loss_function = "squared",
    num_bits = 18L,
    num_passes = 1L,
    pass_through_args = "",
    power_t = 0.5,
    prediction_col = "prediction",
    weight_col = NULL) {
  .py_names <- c(
    batch_size = "batchSize",
    features_col = "featuresCol",
    hash_seed = "hashSeed",
    l1 = "l1",
    l2 = "l2",
    label_col = "labelCol",
    learning_rate = "learningRate",
    loss_function = "lossFunction",
    num_bits = "numBits",
    num_passes = "numPasses",
    pass_through_args = "passThroughArgs",
    power_t = "powerT",
    prediction_col = "predictionCol",
    weight_col = "weightCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$VowpalWabbitRegressor, .args)
}

#' ImageSetAugmenter (generated wrapper over mmlspark_tpu.ops.image_ops.ImageSetAugmenter)
#' @param flip_left_right Add horizontal flips
#' @param flip_up_down Add vertical flips
#' @param input_col Image column
#' @param output_col Output image column
#' @export
ml_image_set_augmenter <- function(
    flip_left_right = TRUE,
    flip_up_down = FALSE,
    input_col = "image",
    output_col = "image") {
  .py_names <- c(
    flip_left_right = "flipLeftRight",
    flip_up_down = "flipUpDown",
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ImageSetAugmenter, .args)
}

#' ImageTransformer (generated wrapper over mmlspark_tpu.ops.image_ops.ImageTransformer)
#' @param input_col Image struct column
#' @param output_col Output image column
#' @param stages Ordered op list
#' @export
ml_image_transformer <- function(
    input_col = "image",
    output_col = "out_image",
    stages = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol",
    stages = "stages")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ImageTransformer, .args)
}

#' UnrollBinaryImage (generated wrapper over mmlspark_tpu.ops.image_ops.UnrollBinaryImage)
#' @param input_col Binary image column
#' @param output_col Unrolled vector column
#' @export
ml_unroll_binary_image <- function(
    input_col = "image",
    output_col = "unrolled") {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$UnrollBinaryImage, .args)
}

#' UnrollImage (generated wrapper over mmlspark_tpu.ops.image_ops.UnrollImage)
#' @param input_col Image struct column
#' @param output_col Unrolled vector column
#' @export
ml_unroll_image <- function(
    input_col = "image",
    output_col = "unrolled") {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$UnrollImage, .args)
}

#' Cacher (generated wrapper over mmlspark_tpu.stages.basic.Cacher)
#' @param disable Pass-through when true
#' @export
ml_cacher <- function(
    disable = FALSE) {
  .py_names <- c(
    disable = "disable")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$Cacher, .args)
}

#' ClassBalancer (generated wrapper over mmlspark_tpu.stages.basic.ClassBalancer)
#' @param broadcast_join unused (API parity)
#' @param input_col Label column
#' @param output_col Weight column
#' @export
ml_class_balancer <- function(
    broadcast_join = FALSE,
    input_col = "label",
    output_col = "weight") {
  .py_names <- c(
    broadcast_join = "broadcastJoin",
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ClassBalancer, .args)
}

#' ClassBalancerModel (generated wrapper over mmlspark_tpu.stages.basic.ClassBalancerModel)
#' @param input_col Label column
#' @param output_col Weight column
#' @param weights level -> weight map
#' @export
ml_class_balancer_model <- function(
    input_col = "label",
    output_col = "weight",
    weights = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol",
    weights = "weights")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ClassBalancerModel, .args)
}

#' DropColumns (generated wrapper over mmlspark_tpu.stages.basic.DropColumns)
#' @param cols Columns to drop
#' @export
ml_drop_columns <- function(
    cols = NULL) {
  .py_names <- c(
    cols = "cols")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$DropColumns, .args)
}

#' EnsembleByKey (generated wrapper over mmlspark_tpu.stages.basic.EnsembleByKey)
#' @param collapse_group One row per key
#' @param cols Columns to ensemble
#' @param keys Grouping key columns
#' @param strategy mean (only supported strategy)
#' @param vector_dims unused (API parity)
#' @export
ml_ensemble_by_key <- function(
    collapse_group = TRUE,
    cols = NULL,
    keys = NULL,
    strategy = "mean",
    vector_dims = NULL) {
  .py_names <- c(
    collapse_group = "collapseGroup",
    cols = "cols",
    keys = "keys",
    strategy = "strategy",
    vector_dims = "vectorDims")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$EnsembleByKey, .args)
}

#' Explode (generated wrapper over mmlspark_tpu.stages.basic.Explode)
#' @param input_col Column of sequences
#' @param output_col Exploded column
#' @export
ml_explode <- function(
    input_col = NULL,
    output_col = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$Explode, .args)
}

#' Lambda (generated wrapper over mmlspark_tpu.stages.basic.Lambda)
#' @param transform_func df -> df callable
#' @export
ml_lambda <- function(
    transform_func = NULL) {
  .py_names <- c(
    transform_func = "transformFunc")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$Lambda, .args)
}

#' MultiColumnAdapter (generated wrapper over mmlspark_tpu.stages.basic.MultiColumnAdapter)
#' @param base_stage Stage with inputCol/outputCol
#' @param input_cols Input columns
#' @param output_cols Output columns
#' @export
ml_multi_column_adapter <- function(
    base_stage = NULL,
    input_cols = NULL,
    output_cols = NULL) {
  .py_names <- c(
    base_stage = "baseStage",
    input_cols = "inputCols",
    output_cols = "outputCols")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$MultiColumnAdapter, .args)
}

#' PartitionConsolidator (generated wrapper over mmlspark_tpu.stages.basic.PartitionConsolidator)
#' @param concurrency Target partition count
#' @param concurrent_timeout unused (API parity)
#' @export
ml_partition_consolidator <- function(
    concurrency = 1L,
    concurrent_timeout = 0.0) {
  .py_names <- c(
    concurrency = "concurrency",
    concurrent_timeout = "concurrentTimeout")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$PartitionConsolidator, .args)
}

#' RenameColumn (generated wrapper over mmlspark_tpu.stages.basic.RenameColumn)
#' @param input_col Existing column name
#' @param output_col New column name
#' @export
ml_rename_column <- function(
    input_col = NULL,
    output_col = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$RenameColumn, .args)
}

#' Repartition (generated wrapper over mmlspark_tpu.stages.basic.Repartition)
#' @param disable Pass-through when true
#' @param n Target number of partitions
#' @export
ml_repartition <- function(
    disable = FALSE,
    n = NULL) {
  .py_names <- c(
    disable = "disable",
    n = "n")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$Repartition, .args)
}

#' SelectColumns (generated wrapper over mmlspark_tpu.stages.basic.SelectColumns)
#' @param cols Columns to keep
#' @export
ml_select_columns <- function(
    cols = NULL) {
  .py_names <- c(
    cols = "cols")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$SelectColumns, .args)
}

#' StratifiedRepartition (generated wrapper over mmlspark_tpu.stages.basic.StratifiedRepartition)
#' @param label_col Label column
#' @param mode native|equal|mixed
#' @param seed Random seed
#' @export
ml_stratified_repartition <- function(
    label_col = "label",
    mode = "native",
    seed = 0L) {
  .py_names <- c(
    label_col = "labelCol",
    mode = "mode",
    seed = "seed")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$StratifiedRepartition, .args)
}

#' SummarizeData (generated wrapper over mmlspark_tpu.stages.basic.SummarizeData)
#' @param basic Include basic stats
#' @param counts Include count stats
#' @param error_threshold Quantile error (unused: exact)
#' @param percentiles Include percentiles
#' @export
ml_summarize_data <- function(
    basic = TRUE,
    counts = TRUE,
    error_threshold = 0.0,
    percentiles = TRUE) {
  .py_names <- c(
    basic = "basic",
    counts = "counts",
    error_threshold = "errorThreshold",
    percentiles = "percentiles")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$SummarizeData, .args)
}

#' TextPreprocessor (generated wrapper over mmlspark_tpu.stages.basic.TextPreprocessor)
#' @param input_col Input text column
#' @param map substring -> replacement map
#' @param norm_func lowerCase|identity pre-normalization
#' @param output_col Output text column
#' @export
ml_text_preprocessor <- function(
    input_col = NULL,
    map = NULL,
    norm_func = "lowerCase",
    output_col = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    map = "map",
    norm_func = "normFunc",
    output_col = "outputCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TextPreprocessor, .args)
}

#' Timer (generated wrapper over mmlspark_tpu.stages.basic.Timer)
#' @param disable_materialization Skip forcing evaluation
#' @param log_to_scala Print timing lines
#' @param stage The wrapped stage
#' @export
ml_timer <- function(
    disable_materialization = TRUE,
    log_to_scala = TRUE,
    stage = NULL) {
  .py_names <- c(
    disable_materialization = "disableMaterialization",
    log_to_scala = "logToScala",
    stage = "stage")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$Timer, .args)
}

#' UDFTransformer (generated wrapper over mmlspark_tpu.stages.basic.UDFTransformer)
#' @param input_col Input column
#' @param input_cols Input columns (multi-arg UDF)
#' @param output_col Output column
#' @param udf The per-value function
#' @export
ml_u_d_f_transformer <- function(
    input_col = NULL,
    input_cols = NULL,
    output_col = NULL,
    udf = NULL) {
  .py_names <- c(
    input_col = "inputCol",
    input_cols = "inputCols",
    output_col = "outputCol",
    udf = "udf")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$UDFTransformer, .args)
}

#' DynamicMiniBatchTransformer (generated wrapper over mmlspark_tpu.stages.minibatch.DynamicMiniBatchTransformer)
#' @param max_batch_size Upper bound on batch size
#' @export
ml_dynamic_mini_batch_transformer <- function(
    max_batch_size = 2147483647L) {
  .py_names <- c(
    max_batch_size = "maxBatchSize")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$DynamicMiniBatchTransformer, .args)
}

#' FixedMiniBatchTransformer (generated wrapper over mmlspark_tpu.stages.minibatch.FixedMiniBatchTransformer)
#' @param batch_size Rows per batch
#' @param buffered unused (API parity)
#' @param max_buffer_size unused (API parity)
#' @export
ml_fixed_mini_batch_transformer <- function(
    batch_size = 10L,
    buffered = FALSE,
    max_buffer_size = 2147483647L) {
  .py_names <- c(
    batch_size = "batchSize",
    buffered = "buffered",
    max_buffer_size = "maxBufferSize")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$FixedMiniBatchTransformer, .args)
}

#' FlattenBatch (generated wrapper over mmlspark_tpu.stages.minibatch.FlattenBatch)
#' @export
ml_flatten_batch <- function(
) {
  .py_names <- c(
)
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$FlattenBatch, .args)
}

#' TimeIntervalMiniBatchTransformer (generated wrapper over mmlspark_tpu.stages.minibatch.TimeIntervalMiniBatchTransformer)
#' @param max_batch_size Upper bound on batch size
#' @param millis_to_wait Window length in ms
#' @export
ml_time_interval_mini_batch_transformer <- function(
    max_batch_size = 2147483647L,
    millis_to_wait = 1000L) {
  .py_names <- c(
    max_batch_size = "maxBatchSize",
    millis_to_wait = "millisToWait")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TimeIntervalMiniBatchTransformer, .args)
}

#' ComputeModelStatistics (generated wrapper over mmlspark_tpu.train.compute_statistics.ComputeModelStatistics)
#' @param evaluation_metric classification|regression|all|<specific metric>
#' @param label_col True label column
#' @param scored_labels_col Predicted label column
#' @param scores_col Probability/score column (classification)
#' @export
ml_compute_model_statistics <- function(
    evaluation_metric = "all",
    label_col = "label",
    scored_labels_col = "prediction",
    scores_col = NULL) {
  .py_names <- c(
    evaluation_metric = "evaluationMetric",
    label_col = "labelCol",
    scored_labels_col = "scoredLabelsCol",
    scores_col = "scoresCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ComputeModelStatistics, .args)
}

#' ComputePerInstanceStatistics (generated wrapper over mmlspark_tpu.train.compute_statistics.ComputePerInstanceStatistics)
#' @param evaluation_metric classification|regression|all
#' @param label_col True label column
#' @param scored_labels_col Predicted label column
#' @param scores_col Probability column
#' @export
ml_compute_per_instance_statistics <- function(
    evaluation_metric = "all",
    label_col = "label",
    scored_labels_col = "prediction",
    scores_col = NULL) {
  .py_names <- c(
    evaluation_metric = "evaluationMetric",
    label_col = "labelCol",
    scored_labels_col = "scoredLabelsCol",
    scores_col = "scoresCol")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$ComputePerInstanceStatistics, .args)
}

#' TrainClassifier (generated wrapper over mmlspark_tpu.train.train_classifier.TrainClassifier)
#' @param features_col Assembled features column
#' @param label_col Label column
#' @param model Inner estimator
#' @param num_features Hash buckets for text columns
#' @export
ml_train_classifier <- function(
    features_col = "features",
    label_col = "label",
    model = NULL,
    num_features = 262144L) {
  .py_names <- c(
    features_col = "featuresCol",
    label_col = "labelCol",
    model = "model",
    num_features = "numFeatures")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TrainClassifier, .args)
}

#' TrainRegressor (generated wrapper over mmlspark_tpu.train.train_classifier.TrainRegressor)
#' @param features_col Assembled features column
#' @param label_col Label column
#' @param model Inner estimator
#' @param num_features Hash buckets for text columns
#' @export
ml_train_regressor <- function(
    features_col = "features",
    label_col = "label",
    model = NULL,
    num_features = 262144L) {
  .py_names <- c(
    features_col = "featuresCol",
    label_col = "labelCol",
    model = "model",
    num_features = "numFeatures")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TrainRegressor, .args)
}

#' TrainedClassifierModel (generated wrapper over mmlspark_tpu.train.train_classifier.TrainedClassifierModel)
#' @param features_col Assembled features column
#' @param featurizer_model Fitted featurizer
#' @param inner_model Fitted inner model
#' @param label_col Label column
#' @param label_levels Original label levels
#' @param model Inner estimator
#' @param num_features Hash buckets for text columns
#' @export
ml_trained_classifier_model <- function(
    features_col = "features",
    featurizer_model = NULL,
    inner_model = NULL,
    label_col = "label",
    label_levels = NULL,
    model = NULL,
    num_features = 262144L) {
  .py_names <- c(
    features_col = "featuresCol",
    featurizer_model = "featurizerModel",
    inner_model = "innerModel",
    label_col = "labelCol",
    label_levels = "labelLevels",
    model = "model",
    num_features = "numFeatures")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TrainedClassifierModel, .args)
}

#' TrainedRegressorModel (generated wrapper over mmlspark_tpu.train.train_classifier.TrainedRegressorModel)
#' @param features_col Assembled features column
#' @param featurizer_model Fitted featurizer
#' @param inner_model Fitted inner model
#' @param label_col Label column
#' @param label_levels Original label levels
#' @param model Inner estimator
#' @param num_features Hash buckets for text columns
#' @export
ml_trained_regressor_model <- function(
    features_col = "features",
    featurizer_model = NULL,
    inner_model = NULL,
    label_col = "label",
    label_levels = NULL,
    model = NULL,
    num_features = 262144L) {
  .py_names <- c(
    features_col = "featuresCol",
    featurizer_model = "featurizerModel",
    inner_model = "innerModel",
    label_col = "labelCol",
    label_levels = "labelLevels",
    model = "model",
    num_features = "numFeatures")
  .args <- as.list(environment())
  .args <- .args[!vapply(.args, is.null, logical(1))]
  .args <- .args[names(.args) %in% names(.py_names)]
  names(.args) <- .py_names[names(.args)]
  .mod <- .mmlspark_tpu_module()
  do.call(.mod$generated_api$TrainedRegressorModel, .args)
}

